"""One run of one cell: set up from the seed, measure the window, check the
answers against the plain reference, read the metrics.

Order matters: the window runs with nothing of the bench's on the device
but the queries; the device's memory peak is read before the program's
state is freed; the reference runs last, on a corpus made again from the
seed, so it neither sets the peak nor counts in ``setup_s``.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import sys
import time
from pathlib import Path

import numpy as np

from . import check, data, loops, roofline, spec, tracing, traffic
from . import system as system_lib


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def use_compile_cache(root: Path = spec.ROOT) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` if
    set, else ``<checkout>/.jax_cache`` (a fixed path: the directory is part
    of each entry's key). Every program is cached, however fast it compiled,
    so a run after the first compiles nothing."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache, and
    the cache's hits, as JAX reports them."""

    def __init__(self):
        self.programs = 0
        self.hits = 0
        self.seconds = 0.0

    def install(self) -> "CompileCounter":
        import jax

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.programs += 1
                self.seconds += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return self

    def snapshot(self) -> tuple:
        return self.programs, self.hits, self.seconds


def devices_for(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


@dataclasses.dataclass
class Run:
    """What a metric reader (``metrics/<name>.py``) reads."""

    cell: spec.Cell
    setup_s: float
    window: loops.Window
    verdict: check.Verdict
    trace: tracing.TraceSummary | None
    peak: dict | None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def batch(self) -> int:
        return int(self.cell.traffic["batch"])

    def latency_s(self) -> np.ndarray:
        """Due time to collection, every answered request due in the window."""
        w = self.window
        lat = (w.done - w.due)[w.in_window]
        return lat[~np.isnan(lat)]

    def late_s(self) -> np.ndarray:
        """How late the generator submitted each request due in the window."""
        w = self.window
        late = (w.submit - w.due)[w.in_window]
        return late[~np.isnan(late)]

    def roofline(self, kernel: str, **shape) -> float | None:
        """Share (%) of its roofline that ``kernel`` reached over the traced
        window, every call at ``shape``; None when the trace holds none."""
        t = self.trace
        if t is None or not t.kernel_calls.get(kernel):
            return None
        return roofline.share(kernel, shape, t.kernel_calls[kernel],
                              t.kernel_s[kernel], self.peak, self.cell.bench)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, make_system=None) -> dict:
    """One run; returns the result line's object. ``make_system(config,
    batch, corpus, key)`` stands in for the program where a test or the
    control asks."""
    if make_system is None:
        system_lib.import_program()  # no program, no run: fail before set-up
    devs = devices_for(cell.chips, require_chip)
    dev = devs[0]
    peak = roofline.peaks(dev.device_kind, cell.bench) if require_chip else None
    compiles = CompileCounter().install()
    cfg, tr = cell.config, cell.traffic
    k = int(cfg["k"])
    n_corpus = int(cfg["corpus_size"])
    rng = np.random.default_rng(seed)

    t = time.perf_counter()
    corpus = data.corpus(seed, cfg)
    due = traffic.arrival_times(tr["arrivals"], seconds, rng)
    rows = traffic.query_rows(tr["queries"], len(due), n_corpus, rng)
    queries = data.queries(seed, cfg, corpus, rows)
    log(f"data: corpus {n_corpus} x {cfg['dim']}, {len(rows)} requests, "
        f"{time.perf_counter() - t:.2f} s")

    t = time.perf_counter()
    system = (make_system or system_lib.build)(
        cfg, int(tr["batch"]), corpus, data.key(seed, data.BUILD))
    del corpus
    gc.collect()
    loops.warm(system, queries, batch=int(tr["batch"]),
               drain_chunk=int(tr.get("dispatches_per_drain", 1)))
    # What set-up allocated stays out of the window's garbage collections,
    # as in a server that has finished starting.
    gc.collect()
    gc.freeze()
    bank = getattr(system.params, "bank", None)
    log(f"build and warm-up: {time.perf_counter() - t:.2f} s; cluster "
        f"capacity {getattr(system.params, 'capacity', None)}; index bytes "
        f"by tier {bank.nbytes_by_tier() if bank is not None else None}")
    traces0 = system.retraces()
    c0 = compiles.snapshot()
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.2f} s; {c0[0]} programs compiled or loaded "
        f"({c0[1]} from the persistent cache) in {c0[2]:.1f} s")

    if trace:
        trace_dir = spec.ROOT / "build" / "bench_trace" / cell.name
        tracing.start(trace_dir)
    try:
        window = loops.open_loop(system, queries, due, seconds=seconds,
                                 drain_chunk=int(tr.get("dispatches_per_drain", 1)))
    finally:
        if trace:
            tracing.stop()
    c1 = compiles.snapshot()
    retraces = system.retraces() - traces0
    log(f"window: {seconds:.2f} s, {int(window.in_window.sum())} requests "
        f"counted, {len(window.answers)} sent; {c1[0] - c0[0]} programs "
        f"compiled or loaded and {retraces} query-path retraces inside it")
    mem = memory_peak(devs)
    summary = None
    if trace:
        kernels = sorted(p.stem for p in (cell.bench / "work").glob("*.py"))
        summary = tracing.reduce(tracing.extract(
            tracing.find_xplane(trace_dir), kernels))
        log(f"trace: busy {summary.busy_s:.4f} s of {summary.window_s:.4f} s; "
            f"kernels {summary.kernel_calls}")

    del system
    gc.unfreeze()
    gc.collect()
    t = time.perf_counter()
    corpus = data.corpus(seed, cfg)
    reference = spec.load_module("references", cfg["reference"], cell.bench)
    verdict = check.judge(reference, corpus, queries, window.answers, k=k,
                          limit=float(cfg["check"]["score_err_limit"]),
                          recall_floor=float(cfg["check"]["recall_floor"]))
    del corpus
    log(f"reference check: {time.perf_counter() - t:.2f} s")

    ctx = Run(cell=cell, setup_s=setup_s, window=window,
              verdict=verdict, trace=summary, peak=peak)
    metrics = {}
    for m in cell.metrics(trace):
        value = spec.load_module("metrics", m["name"], cell.bench).read(ctx)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
            continue
        if not math.isfinite(value):
            raise RuntimeError(f"metric {m['name']} is {value}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    out = {"correct": verdict.correct, "attempted": verdict.attempted,
           "failed": verdict.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["limits"] = verdict.limits()
    return out

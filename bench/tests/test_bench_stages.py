"""The reduction from a profiler trace to device time per search stage and
idle time per engine span: a trace written by hand with unscoped ops, the
recorded trace of a program without stage scopes
(``trace_v5e_int8dev.json``), and one recorded with them
(``trace_v5e_int4host.json``, a traced int4 run cut to ~2 batches)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchlib import spec, stages, tracing

HERE = Path(__file__).resolve().parent

# Times in ns. Window [0, 1000]; engine spans nest inside bench spans, JAX's
# own annotations inside engine spans.
HAND = {
    "host": [
        ["bench.window", 0.0, 1000.0],
        ["bench.submit", 20.0, 40.0],
        ["bench.drain", 100.0, 500.0],
        ["engine.dispatch", 110.0, 20.0],
        ["PjitFunction(f)", 112.0, 15.0],
        ["engine.wait", 130.0, 240.0],
        ["engine.d2h", 400.0, 100.0],
        ["np.asarray(jax.Array)", 401.0, 98.0],
        ["bench.sleep", 650.0, 300.0],
    ],
    "device": {"0": [
        ["fusion.1", 100.0, 40.0, None],
        ["sketch_prefilter", 150.0, 100.0, "sketch_prefilter"],
        ["copy.2", 250.0, 30.0, None],
        ["fused_verify_int4", 300.0, 60.0, "fused_verify_int4"],
        ["fusion.3", 500.0, 20.0, None],
        ["fusion.5", 560.0, 10.0, None],
        ["fusion.4", 990.0, 20.0, None],
        ["after", 2000.0, 10.0, None],
    ]},
    "stage": {"0": [
        "lider.candidates", "lider.sketch", None, "lider.code_pass", None,
        None, "lider.rescore", "lider.rescore",
    ]},
}


def test_stage_seconds_and_unattributed_ops():
    s = stages.reduce(HAND)
    assert s.stage_s == pytest.approx({
        "lider.candidates": 40e-9, "lider.sketch": 100e-9,
        "lider.code_pass": 60e-9, "lider.rescore": 20e-9,
        stages.UNATTRIBUTED: 60e-9,
    })
    assert [k for k, _ in s.unattributed] == [
        "copy.2", "fusion.3", "fusion.5"]
    assert s.op_s == pytest.approx(280e-9)  # "after" is past the window
    assert sum(s.stage_s.values()) == pytest.approx(s.op_s)
    assert s.coverage == pytest.approx(1 - 60 / 280)


def test_idle_goes_to_the_innermost_engine_span():
    """Each gap goes by its midpoint to the innermost ``engine.*`` span,
    not to the JAX annotation inside it, under its ``bench.*`` span."""
    idle = dict(stages.reduce(HAND).idle)
    assert idle == pytest.approx({
        "bench.submit": 100e-9,  # [0, 100]
        "bench.drain/engine.wait": 30e-9,  # [140, 150], [280, 300]
        "bench.drain/engine.d2h": 140e-9,  # [360, 500]
        "bench.drain": 40e-9,  # [520, 560]: engine code outside any span
        "bench.sleep": 420e-9,  # [570, 990]
    })
    s = tracing.reduce(HAND)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_a_trace_without_stages_reduces_as_before():
    """The kernels' reduction ignores the stage lists, and without them
    every op is unattributed."""
    bare = {k: v for k, v in HAND.items() if k != "stage"}
    assert tracing.reduce(HAND) == tracing.reduce(bare)
    s = stages.reduce(bare)
    assert s.stage_s == pytest.approx({stages.UNATTRIBUTED: 280e-9})
    assert s.coverage == 0.0


def test_recorded_unscoped_trace_keeps_its_numbers():
    """The v5e trace recorded before the program had stage scopes: the
    kernels' reduction gives what it gave, the stages' puts all under
    ``unattributed``, and a stage metric would read nothing there."""
    fx = json.loads((HERE / "trace_v5e_int8dev.json").read_text())
    t = tracing.reduce(fx)
    assert t.kernel_calls == {"fused_verify_float": 2, "fused_verify_int8": 1}
    assert t.device_ops[0][0] == "fused_verify_int8"
    s = stages.reduce(fx)
    w = fx["host"][0][2]
    inside = [o for o in fx["device"]["0"] if o[1] < w and o[1] + o[2] > 0]
    assert s.stage_s == pytest.approx(
        {stages.UNATTRIBUTED: sum(o[2] for o in inside) * 1e-9})
    assert sum(v for _, v in s.idle) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-6)
    run = _run(s, {"n_batches": 2})
    for name in ("route_ms.open", "candidates_ms.open", "sketch_ms.open",
                 "code_pass_ms.open", "rescore_ms.open"):
        assert spec.load_module("metrics", name).read(run) is None


def recorded_scoped():
    return json.loads((HERE / "trace_v5e_int4host.json").read_text())


# The one large op the compiler emits with no op metadata, so no stage: a
# relayout copy of the bank's (c, H, Lp) sorted-position table that layout
# assignment puts in front of the candidates' reshape.
NO_METADATA = {"copy.182 s32[1024,10,2816]"}


def test_recorded_scoped_trace_stage_sums_close():
    """Two int4 host-tier batches on a v5e chip: the stage sums and the
    unattributed remainder add up to the summed device op time."""
    fx = recorded_scoped()
    s = stages.reduce(fx)
    w = fx["host"][0][2]
    inside = [o for o in fx["device"]["0"] if o[1] < w and o[1] + o[2] > 0]
    assert s.op_s == pytest.approx(sum(o[2] for o in inside) * 1e-9)
    assert sum(s.stage_s.values()) == pytest.approx(s.op_s)
    assert set(s.stage_s) == set(stages.STAGES) | {stages.UNATTRIBUTED}
    assert s.coverage > 0.95
    assert s.stage_s["lider.sketch"] == max(s.stage_s.values())
    t = tracing.reduce(fx)
    assert t.kernel_calls == {"sketch_prefilter": 2, "fused_verify_int4": 2,
                              "fused_verify_float": 5}
    assert sum(v for _, v in s.idle) == pytest.approx(
        t.window_s - t.busy_s, rel=1e-6)
    assert all(k.startswith("bench.") for k, _ in s.idle)


def test_recorded_scoped_trace_top_ops_carry_a_stage():
    """Every call of each op among the ten largest carries a stage, but
    for the copy with no metadata; each kernel runs in the stages the
    program names (``fused_verify_float`` routes and rescores)."""
    fx = recorded_scoped()
    top = [label for label, _ in tracing.reduce(fx).device_ops]
    by_label = {}
    for op, stage in zip(fx["device"]["0"], fx["stage"]["0"]):
        by_label.setdefault(op[0], set()).add(stage)
    assert {label for label in top if None in by_label[label]} == NO_METADATA
    assert by_label["sketch_prefilter"] == {"lider.sketch"}
    assert by_label["fused_verify_int4"] == {"lider.code_pass"}
    assert by_label["fused_verify_float"] == {"lider.route", "lider.rescore"}


def _run(summary, stats):
    """What a metric reader reads, with the stage summary already made."""
    return SimpleNamespace(trace=object(), stages=summary,
                           window=SimpleNamespace(stats=stats))


def test_stage_metrics_read_device_ms_per_batch():
    run = _run(stages.reduce(HAND), {"n_batches": 2})
    read = {name: spec.load_module("metrics", f"{name}_ms.open").read(run)
            for name in ("route", "candidates", "sketch", "code_pass",
                         "rescore")}
    assert read.pop("route") is None  # no op of that stage in the trace
    # HAND's stage seconds over 2 batches, in ms.
    assert read == pytest.approx({"candidates": 20e-6, "sketch": 50e-6,
                                  "code_pass": 30e-6, "rescore": 10e-6})


# The engine's counters over a window, as the program before its spans
# (no transfer or queue counters) and after them.
OLD_STATS = {"n_queries": 64, "n_batches": 2, "n_padded": 0,
             "n_cache_hits": 0, "host_fetch_us": 2000.0, "n_host_fetches": 2}
NEW_STATS = dict(OLD_STATS, h2d_us=800.0, h2d_bytes=7, d2h_us=5000.0,
                 n_d2h=2, queue_wait_us=64e3, service_us=128e4)


@pytest.mark.parametrize("name,old,new", [
    ("queue_wait_ms.open", None, 1.0),
    ("service_ms.open", None, 20.0),
    ("h2d_ms.open", None, 0.4),
    ("d2h_ms.open", None, 2.5),
    ("host_fetch_ms.open", 1.0, 1.0),
])
def test_counter_metrics_read_nothing_from_a_program_without_them(
        name, old, new):
    reader = spec.load_module("metrics", name)
    got_old = reader.read(SimpleNamespace(window=SimpleNamespace(
        stats=OLD_STATS)))
    got_new = reader.read(SimpleNamespace(window=SimpleNamespace(
        stats=NEW_STATS)))
    assert got_old == (None if old is None else pytest.approx(old))
    assert got_new == pytest.approx(new)


def test_cpu_trace_extracts_like_the_kernels_reduction(tmp_path):
    """On a trace with no device plane, ``extract`` gives what
    ``tracing.extract`` gives and no stages; its proto schema reads the
    host plane's spans."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(4)
    f(x).block_until_ready()
    tracing.start(tmp_path)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("engine.h2d", batch=7):
                f(x).block_until_ready()
    finally:
        tracing.stop()
    path = tracing.find_xplane(tmp_path)
    ex = stages.extract(path, ["sketch_prefilter"])
    assert ex["stage"] == {} and ex["device"] == {}
    assert {h[0] for h in ex["host"]} >= {tracing.WINDOW_SPAN, "engine.h2d"}
    space = stages._xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    host = [p for p in space.planes if p.name == "/host:CPU"][0]
    names = {e.value.name for e in host.event_metadata}
    assert {tracing.WINDOW_SPAN, "engine.h2d"} <= names

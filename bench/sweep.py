#!/usr/bin/env python3
"""Knee sweep of a cell: one build, then one window per offered
rate, on the cell's configuration, batch and query mix.

For each rate it prints the requests sent, the median and 99th percentile
latency from due time, and the backlog (sent, not yet answered) at the
window's start and end. The knee is the highest rate whose backlog is no
longer at the end than at the start; the cell's traffic file then fixes
its rate at about four fifths of it (PERF.md records the sweep).

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 200 400 600
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import data, loops, runner, spec, traffic  # noqa: E402
from benchlib import system as system_lib  # noqa: E402


def backlog(w: loops.Window, t: float) -> int:
    return int(np.sum(w.submit <= t) - np.sum(w.done <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    cfg, tr = cell.config, cell.traffic
    runner.use_compile_cache()
    runner.devices_for(cell.chips, require_chip=True)
    rng = np.random.default_rng(args.seed)
    n = int(max(args.rates) * args.seconds * 1.1) + 1
    corpus = data.corpus(args.seed, cfg)
    rows = traffic.query_rows(tr["queries"], n, int(cfg["corpus_size"]), rng)
    queries = data.queries(args.seed, cfg, corpus, rows)
    system = system_lib.build(cfg, int(tr["batch"]), corpus,
                              data.key(args.seed, data.BUILD))
    del corpus
    chunk = int(tr.get("dispatches_per_drain", 1))
    loops.warm(system, queries, batch=int(tr["batch"]), drain_chunk=chunk)
    print(f"set-up {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    for rate in args.rates:
        arr = dict(tr["arrivals"], rate_qps=rate)
        due = traffic.arrival_times(arr, args.seconds, rng)
        w = loops.open_loop(system, queries[:len(due)], due,
                            seconds=args.seconds, drain_chunk=chunk)
        lat = (w.done - w.due)[w.in_window]
        lat = lat[~np.isnan(lat)] * 1e3
        st = w.stats
        print(json.dumps({
            "workload": cell.name, "rate_qps": rate, "sent": len(due),
            "answered": int(np.sum(~np.isnan(w.done))),
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "backlog_start": backlog(w, 0.5), "backlog_end": backlog(w, args.seconds),
            "batches": st["n_batches"],
            "batch_fill": st["n_queries"] / max(st["n_queries"] + st["n_padded"], 1),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

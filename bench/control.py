#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` (PERF.md): a cell's run, on
its own traffic and seeds, with the program as it is or with something put
in its place. The benchmark's own runs never run this.

  none     the program as it is: sound readings, the lower ends;
  control  the plain reference one precision below the configuration's
           (bfloat16 for float32), served through the engine in the
           program's place: it has to come out as not correct, and its
           ``score_err`` sets that limit's upper end;
  routing, sketch, codes
           the program with that fault planted ahead of the rescore
           (``benchlib/faults.py``): each has to come out as not correct,
           and its recall sets the recall floor's upper end.

One build per seed serves every plant of that seed but the control.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13 \
        --plant none control routing
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import faults, runner, spec, system  # noqa: E402

PLANTS = ("none", "control") + faults.KINDS


def control_system(reference):
    def make(config, batch, corpus, _key):
        return system.engine_over(reference.control_search(corpus), config,
                                  batch)
    return make


def built_once():
    """A ``make_system`` that builds the program once and serves that
    system again on every later call."""
    cache = []

    def make(config, batch, corpus, key):
        if not cache:
            cache.append(system.build(config, batch, corpus, key))
        return cache[0]
    return make


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--plant", nargs="+", choices=PLANTS, default=["control"])
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    runner.use_compile_cache()
    reference = spec.load_module("references", cell.config["reference"])
    for seed in args.seeds:
        program = built_once()
        for plant in args.plant:
            make = control_system(reference) if plant == "control" else program
            t = time.perf_counter()
            with faults.planted(None if plant in ("none", "control") else plant):
                out = runner.run(cell, seed, args.seconds, False, t_start=t,
                                 make_system=make)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "plant": plant, "correct": out["correct"],
                              "failed": out["failed"],
                              "attempted": out["attempted"],
                              "limits": out["limits"],
                              "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

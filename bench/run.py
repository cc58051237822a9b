#!/usr/bin/env python3
"""Run one cell of the LIDER serving benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs``) and a traffic mix (``bench/traffic``). The run makes the
corpus and the queries from ``--seed``, builds the index through the
program's normal entry points and warms it (set-up), drives it for
``--seconds`` (the window), then checks every answer against the plain
reference. ``--trace 1`` records a profiler trace of the window and reports
the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``limits``: each number compared, beside its limit; the same
numbers are the last lines of standard error. Without a TPU, with fewer
chips than the cell needs, or without the program (``src/repro``), the run
prints no result and exits non-zero.
"""
import time

T_START = time.perf_counter()  # set-up is timed from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import runner, spec  # noqa: E402
from benchlib.roofline import RooflineError, UnknownDevice  # noqa: E402
from benchlib.system import ProgramMissing  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        runner.use_compile_cache()
        out = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START)
    except (spec.SpecError, runner.NoChip, ProgramMissing, UnknownDevice,
            RooflineError) as e:
        print(f"bench/run.py: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    for name, v in out["limits"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

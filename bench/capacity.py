#!/usr/bin/env python3
"""The largest cluster that a configuration's build makes, seed by seed.

A configuration fixes ``lider.capacity``, the slots per cluster, so that
every seed builds the same shapes; a seed whose largest cluster holds more
rows than that cannot build (``CapacityOverflowError``). This runs the
build's first stage alone, exactly as ``build_lider`` does it from the
seed's build key (k-means over the seeded corpus), and prints each seed's
largest cluster beside the capacity (PERF.md records the readings).

    python3 bench/capacity.py --config msmarco768-int4-host --seeds 1 2 3
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import data, runner, spec, system  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cfg = spec.load_json(spec.BENCH / "configs" / f"{spec.check_name(args.config)}.json")
    _, lider = system.import_program()
    import jax
    import jax.numpy as jnp

    runner.use_compile_cache()
    lcfg = lider.LiderConfig(**cfg["lider"])
    for seed in args.seeds:
        corpus = data.corpus(seed, cfg)
        rng_km = jax.random.split(data.key(seed, data.BUILD), 3)[0]
        km = lider.assign_points(rng_km, corpus, lcfg)
        sizes = jnp.bincount(km.assignment, length=lcfg.n_clusters)
        print(json.dumps({"config": args.config, "seed": seed,
                          "largest": int(sizes.max()),
                          "capacity": lcfg.capacity,
                          "mean": cfg["corpus_size"] / lcfg.n_clusters}),
              flush=True)
        del corpus, km
    return 0


if __name__ == "__main__":
    sys.exit(main())

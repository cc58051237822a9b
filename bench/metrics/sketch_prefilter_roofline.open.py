"""Kernels: share of its roofline that ``sketch_prefilter`` reached over
the traced window (%). Each call is the 1-bit pass over a batch's
n_probe * n_arrays * r0 * k candidates per query, keeping
sketch_factor * rescore_factor * k survivors."""


def read(run):
    cfg, lider = run.config, run.config["lider"]
    k = int(cfg["k"])
    return run.roofline(
        "sketch_prefilter", batch=run.batch,
        candidates=lider["n_probe"] * lider["n_arrays"] * lider["r0"] * k,
        dim=int(cfg["dim"]),
        k=cfg["backend"]["sketch_factor"] * lider["rescore_factor"] * k)

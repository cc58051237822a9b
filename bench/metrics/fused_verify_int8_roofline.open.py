"""Kernels: share of its roofline that ``fused_verify_int8`` reached over
the traced window (%). Each call is the int8 first pass over a batch's
n_probe * n_arrays * r0 * k candidates per query, keeping rescore_factor * k."""


def read(run):
    cfg, lider = run.config, run.config["lider"]
    k = int(cfg["k"])
    return run.roofline(
        "fused_verify_int8", batch=run.batch,
        candidates=lider["n_probe"] * lider["n_arrays"] * lider["r0"] * k,
        dim=int(cfg["dim"]), k=lider["rescore_factor"] * k)

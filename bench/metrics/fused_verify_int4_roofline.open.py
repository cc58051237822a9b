"""Kernels: share of its roofline that ``fused_verify_int4`` reached over
the traced window (%). Each call is the int4 pass over a batch's
sketch_factor * rescore_factor * k sketch survivors per query, keeping
rescore_factor * k for the exact rescore."""


def read(run):
    cfg, lider = run.config, run.config["lider"]
    kp = lider["rescore_factor"] * int(cfg["k"])
    return run.roofline(
        "fused_verify_int4", batch=run.batch,
        candidates=cfg["backend"]["sketch_factor"] * kp,
        dim=int(cfg["dim"]), k=kp)

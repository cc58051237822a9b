"""Mean recall@10 of every answer counted in the window against the plain
reference's exact top-10 over the same seeded corpus."""
import numpy as np


def read(run):
    r = run.verdict.recall[run.window.in_window]
    r = r[~np.isnan(r)]
    return float(r.mean()) if r.size else None

"""Load generator: 99th percentile of how late the bench submitted a
request after it fell due, over the window (ms). A starved generator shows
here, not as a fast server."""
import numpy as np


def read(run):
    late = run.late_s()
    return float(np.percentile(late, 99) * 1e3) if late.size else None

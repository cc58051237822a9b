"""Median latency over every request due in the window: from its scheduled
arrival to the collection of its answer (ms)."""
import numpy as np


def read(run):
    lat = run.latency_s()
    return float(np.percentile(lat, 50) * 1e3) if lat.size else None

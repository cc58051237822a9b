"""Search stages: device time of the ``lider.route`` scope (centroid routing
and probe pruning) per batch over the traced window (ms)."""
from benchlib import stages


def read(run):
    return stages.per_batch_ms(run, "lider.route")

"""Search stages: device time of the ``lider.sketch`` scope (the binary-
sketch pre-filter) per batch over the traced window (ms)."""
from benchlib import stages


def read(run):
    return stages.per_batch_ms(run, "lider.sketch")

"""Search stages: device time of the ``lider.rescore`` scope (the exact
float32 rescore and the row-to-id map) per batch over the traced window
(ms)."""
from benchlib import stages


def read(run):
    return stages.per_batch_ms(run, "lider.rescore")

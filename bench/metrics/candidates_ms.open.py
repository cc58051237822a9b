"""Search stages: device time of the ``lider.candidates`` scope (candidate
generation: hash, rescale, RMI, window and the position and id gathers)
per batch over the traced window (ms)."""
from benchlib import stages


def read(run):
    return stages.per_batch_ms(run, "lider.candidates")

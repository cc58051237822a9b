"""Search stages: device time of the ``lider.code_pass`` scope (the int8 or
int4 first pass over the code table) per batch over the traced window
(ms)."""
from benchlib import stages


def read(run):
    return stages.per_batch_ms(run, "lider.code_pass")

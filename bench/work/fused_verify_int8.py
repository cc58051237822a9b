"""``fused_verify_int8``: one call scores ``candidates`` int8 code rows of
width ``dim`` per query of a batch and keeps the top ``k``.

HBM reads: each candidate's codes (dim bytes, gathered by XLA into the
kernel's input tile), its id (int32) and its combined row x query scale
(float32); each query's int8 codes; writes k (id, score) pairs per query.
Operations: an int8 x int8 -> int32 product per code (MXU).
"""


def work(*, batch: int, candidates: int, dim: int, k: int) -> dict:
    nbytes = batch * (candidates * (dim + 8) + dim + 8 * k)
    return {"bytes": nbytes, "ops": {"int8": 2 * batch * candidates * dim}}

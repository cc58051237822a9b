"""``fused_verify_float``: one call scores ``candidates`` float32 rows of
width ``dim`` per query of a batch and keeps the top ``k``.

HBM reads: each candidate's row (4*dim bytes) and id (int32), each query
(4*dim bytes); writes k (id, score) pairs per query. Operations: one
multiply-add per element, at float32 (the kernel asks for HIGHEST, several
bfloat16 passes); bounded here by the bfloat16 peak, the fastest the MXU
can go, so the share is never overstated.
"""


def work(*, batch: int, candidates: int, dim: int, k: int) -> dict:
    nbytes = batch * (candidates * (4 * dim + 4) + 4 * dim + 8 * k)
    return {"bytes": nbytes, "ops": {"bf16": 2 * batch * candidates * dim}}

"""``fused_verify_int4``: as ``fused_verify_int8`` over packed int4 codes,
two per byte (dim/2 bytes a row), unpacked to int8 in VMEM before the same
int8 x int8 -> int32 product (MXU)."""


def work(*, batch: int, candidates: int, dim: int, k: int) -> dict:
    nbytes = batch * (candidates * (dim // 2 + 8) + dim + 8 * k)
    return {"bytes": nbytes, "ops": {"int8": 2 * batch * candidates * dim}}

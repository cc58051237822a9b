"""``sketch_prefilter``: one call scores ``candidates`` rows per query of a
batch by Hamming distance between 1-bit sign sketches, keeping the top ``k``.

What the call has to read from HBM: each candidate's sketch, d/8 bytes
(XLA gathers them into the kernel's input tile), and its id (int32); each
query's sketch; it writes k (id, score) pairs per query. The Hamming sum
is a bfloat16 product with a ones vector over the d/32 popcount words
(MXU); XOR, popcount and the top-k merge run on the vector unit, whose
rate the peaks table does not list, so they set no bound.
"""


def work(*, batch: int, candidates: int, dim: int, k: int) -> dict:
    words = -(-dim // 32)
    nbytes = batch * (candidates * (4 * words + 4) + 4 * words + 8 * k)
    return {"bytes": nbytes, "ops": {"bf16": 2 * batch * candidates * words}}

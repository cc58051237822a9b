"""Work per kernel call, peaks and roofline shares."""
import pytest

from benchlib import roofline, spec

V5E = "TPU v5 lite"


def work(kernel, **shape):
    return spec.load_module("work", kernel).work(**shape)


def test_sketch_prefilter_work_from_shapes():
    # 32 queries x 8,000 candidates at d=768: 96 B sketch + 4 B id each.
    w = work("sketch_prefilter", batch=32, candidates=8000, dim=768, k=160)
    assert w["bytes"] == 32 * (8000 * (96 + 4) + 96 + 8 * 160)
    assert w["ops"] == {"bf16": 2 * 32 * 8000 * 24}


@pytest.mark.parametrize("kernel,row_bytes,unit", [
    ("fused_verify_int8", 768, "int8"),
    ("fused_verify_int4", 384, "int8"),
])
def test_code_pass_work_from_shapes(kernel, row_bytes, unit):
    w = work(kernel, batch=32, candidates=8000, dim=768, k=40)
    assert w["bytes"] == 32 * (8000 * (row_bytes + 8) + 768 + 8 * 40)
    assert w["ops"] == {unit: 2 * 32 * 8000 * 768}


def test_float_rescore_work_from_shapes():
    w = work("fused_verify_float", batch=256, candidates=40, dim=768, k=10)
    assert w["bytes"] == 256 * (40 * (4 * 768 + 4) + 4 * 768 + 8 * 10)
    assert w["ops"] == {"bf16": 2 * 256 * 40 * 768}


def test_peaks_of_v5e_and_unknown_device():
    p = roofline.peaks(V5E)
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v99")


def test_least_time_is_the_larger_bound():
    p = roofline.peaks(V5E)
    mem = {"bytes": 819e9, "ops": {"int8": 1.0}}
    assert roofline.least_seconds(mem, p) == pytest.approx(1.0)
    mxu = {"bytes": 1.0, "ops": {"int8": 2 * 393e12}}
    assert roofline.least_seconds(mxu, p) == pytest.approx(2.0)


def test_share_of_roofline():
    p = roofline.peaks(V5E)
    shape = dict(batch=32, candidates=8000, dim=768, k=40)
    least = roofline.least_seconds(work("fused_verify_int8", **shape), p)
    # 10 calls that took 4x their least time in all: 25%.
    assert roofline.share("fused_verify_int8", shape, 10, 40 * least, p) == \
        pytest.approx(25.0)


def test_share_over_100_percent_is_an_error():
    p = roofline.peaks(V5E)
    shape = dict(batch=32, candidates=8000, dim=768, k=40)
    least = roofline.least_seconds(work("fused_verify_int8", **shape), p)
    with pytest.raises(roofline.RooflineError):
        roofline.share("fused_verify_int8", shape, 10, 9 * least, p)

"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on small traces: one written by hand, one recorded on a TPU v5e chip
(``trace_v5e_int8dev.json``, cut to a short stretch of its window)."""
import json
from pathlib import Path

import pytest

from benchlib import tracing

HERE = Path(__file__).resolve().parent

# Times in ns. Window [0, 1000]; device ops A..E; host spans nest.
HAND = {
    "host": [
        ["bench.window", 0.0, 1000.0],
        ["bench.submit", 60.0, 30.0],
        ["bench.drain", 100.0, 400.0],
        ["PjitFunction(f)", 150.0, 50.0],
        ["bench.sleep", 600.0, 360.0],
    ],
    "device": {"0": [
        ["x", -50.0, 100.0, None],
        ["custom-call.1", 120.0, 40.0, "sketch_prefilter"],
        ["fusion.2", 190.0, 100.0, None],
        ["custom-call.3", 250.0, 100.0, "sketch_prefilter"],
        ["y", 950.0, 100.0, None],
        ["after", 2000.0, 10.0, None],
    ]},
}


def test_busy_is_the_union_of_ops_inside_the_window():
    s = tracing.reduce(HAND)
    assert s.window_s == pytest.approx(1000e-9)
    # [0,50] + [120,160] + [190,350] + [950,1000]
    assert s.busy_s == pytest.approx(300e-9)
    assert s.idle_share == pytest.approx(0.7)


def test_kernel_time_and_calls():
    s = tracing.reduce(HAND)
    assert s.kernel_calls == {"sketch_prefilter": 1 + 1}
    assert s.kernel_s["sketch_prefilter"] == pytest.approx(140e-9)
    ops = dict(s.device_ops)
    assert ops["sketch_prefilter"] == pytest.approx(140e-9)
    assert "after" not in ops


def test_gaps_go_to_the_host_span_at_their_midpoint():
    gaps = dict(tracing.reduce(HAND).idle_gaps)
    assert gaps == pytest.approx({
        "bench.submit": 70e-9,  # [50, 120]
        "bench.drain/PjitFunction(f)": 30e-9,  # [160, 190]
        "bench.sleep": 600e-9,  # [350, 950]
    })


def test_breakdown_is_sorted_and_short():
    s = tracing.reduce(HAND)
    for lst in (s.device_ops, s.idle_gaps):
        assert len(lst) <= tracing.TOP
        assert [v for _, v in lst] == sorted((v for _, v in lst), reverse=True)


@pytest.mark.parametrize("texts,kernel", [
    (["sketch_prefilter"], "sketch_prefilter"),
    (["sketch_prefilter.3"], "sketch_prefilter"),
    (["custom-call.7", "jit(f)/jit(main)/sketch_prefilter/pallas_call"],
     "sketch_prefilter"),
    (["fused_verify_grouped_int8"], None),
    (["fusion.12", "jit(f)/dot_general"], None),
])
def test_kernel_of(texts, kernel):
    kernels = ["fused_verify_float", "fused_verify_int4", "fused_verify_int8",
               "sketch_prefilter"]
    assert tracing.kernel_of(texts, kernels) == kernel


def recorded():
    return json.loads((HERE / "trace_v5e_int8dev.json").read_text())


def test_recorded_trace_busy_against_a_time_grid():
    """Busy time of the recorded stretch against a 10 ns occupancy grid."""
    import numpy as np

    fx = recorded()
    s = tracing.reduce(fx)
    win = fx["host"][0]
    assert win[0] == tracing.WINDOW_SPAN
    grid = np.zeros(int(win[2] / 10) + 1, bool)
    for _, start, dur, _ in fx["device"]["0"]:
        a, b = max(start, 0.0), min(start + dur, win[2])
        if b > a:
            grid[int(round(a / 10)):int(round(b / 10))] = True
    assert s.busy_s == pytest.approx(grid.sum() * 10e-9, rel=1e-3)
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)


def test_recorded_trace_kernels():
    """One int8 device-tier batch: the route and the rescore run
    ``fused_verify_float``, the first pass ``fused_verify_int8``, once each;
    no op label keeps HLO text."""
    s = tracing.reduce(recorded())
    assert s.kernel_calls == {"fused_verify_float": 2, "fused_verify_int8": 1}
    assert s.device_ops[0][0] == "fused_verify_int8"
    assert all("%" not in name and len(name) <= 80 for name, _ in s.device_ops)
    assert s.idle_gaps[0][0] == "bench.drain"


def test_window_span_is_required():
    with pytest.raises(ValueError):
        tracing.reduce({"host": [], "device": HAND["device"]})


def test_cpu_trace_has_host_spans_and_no_device():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(4)
    f(x).block_until_ready()
    d = Path(__import__("tempfile").mkdtemp())
    tracing.start(d)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.drain"):
                f(x).block_until_ready()
    finally:
        tracing.stop()
    ex = tracing.extract(tracing.find_xplane(d), ["sketch_prefilter"])
    names = [h[0] for h in ex["host"]]
    assert tracing.WINDOW_SPAN in names and "bench.drain" in names
    assert ex["device"] == {}
    with pytest.raises(ValueError):
        tracing.reduce(ex)

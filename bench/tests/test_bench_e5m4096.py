"""The d=4096 cell ``e5m4096-int4host-poisson`` resolves from its files,
and the three metrics added with it read what they should: the host tier's
fetch rate from the engine's counters, and the roofline shares of
``sketch_prefilter`` and ``fused_verify_int4`` from a trace."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchlib import roofline, runner, spec, tracing

HERE = Path(__file__).resolve().parent
CELL = "e5m4096-int4host-poisson"
NEW = ("host_fetch_gbps.open", "sketch_prefilter_roofline.open",
       "fused_verify_int4_roofline.open")


def test_cell_resolves_with_its_metrics():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config["dim"] == 4096
    assert cell.config["lider"]["storage_dtype"] == "int4"
    assert cell.config["lider"]["rescore_tier"] == "host"
    assert cell.traffic["batch"] == 32
    assert cell.traffic["dispatches_per_drain"] == 2
    assert cell.traffic["queries"] == {"kind": "fresh"}
    assert {m["name"] for m in cell.end_to_end} == {
        "p50_ms", "recall_at_10", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer}
    # Every per-layer metric of the d=768 int4 cell, and the new ones.
    assert per_layer == {m["name"] for m in
                         spec.load_cell("int4host-poisson").per_layer}
    assert set(NEW) <= per_layer
    for m in cell.per_layer:
        assert callable(spec.load_module("metrics", m["name"]).read)


def _stats_run(**stats):
    return SimpleNamespace(window=SimpleNamespace(stats=stats))


def test_host_fetch_rate_is_bytes_over_time():
    read = spec.load_module("metrics", "host_fetch_gbps.open").read
    # Two fetches of a d=4096 batch's 32 x 40 rows in 4 ms: 10.49 GB/s.
    nbytes = 2 * 32 * 40 * 4096 * 4
    got = read(_stats_run(host_fetch_bytes=nbytes, host_fetch_us=4000.0,
                          n_host_fetches=2))
    assert got == pytest.approx(nbytes / 4e-3 / 1e9)
    # A program without the byte counter, or a window with no fetch.
    assert read(_stats_run(host_fetch_us=4000.0, n_host_fetches=2)) is None
    assert read(_stats_run(host_fetch_bytes=0, host_fetch_us=0.0)) is None


def _trace_run(cell):
    fx = json.loads((HERE / "trace_v5e_int4host.json").read_text())
    summary = tracing.reduce({"host": fx["host"], "device": fx["device"]})
    return runner.Run(cell=spec.load_cell(cell), setup_s=0.0, window=None,
                      verdict=None, trace=summary,
                      peak=roofline.peaks("TPU v5 lite"))


@pytest.mark.parametrize("cell", ["int4host-poisson", CELL])
def test_roofline_readers_on_a_recorded_int4_trace(cell):
    """On the recorded int4 trace (two batches: one ``sketch_prefilter`` and
    one ``fused_verify_int4`` call each), each share is the calls' least
    time at the cell's shapes over their device time: 8,000 candidates
    keeping 160, then 160 keeping 40, batch 32."""
    run = _trace_run(cell)
    dim = run.config["dim"]
    t = run.trace
    assert t.kernel_calls["sketch_prefilter"] == 2
    assert t.kernel_calls["fused_verify_int4"] == 2
    shapes = {
        "sketch_prefilter": dict(batch=32, candidates=8000, dim=dim, k=160),
        "fused_verify_int4": dict(batch=32, candidates=160, dim=dim, k=40),
    }
    for kernel, shape in shapes.items():
        work = spec.load_module("work", kernel).work(**shape)
        want = 100.0 * 2 * roofline.least_seconds(work, run.peak) / \
            t.kernel_s[kernel]
        got = spec.load_module("metrics", f"{kernel}_roofline.open").read(run)
        assert 0.0 < got == pytest.approx(want)


def test_roofline_readers_read_nothing_without_the_kernel():
    run = _trace_run(CELL)
    run.trace.kernel_calls.pop("sketch_prefilter")
    run.trace.kernel_calls.pop("fused_verify_int4")
    for name in NEW[1:]:
        assert spec.load_module("metrics", name).read(run) is None
    untraced = runner.Run(cell=run.cell, setup_s=0.0, window=None,
                          verdict=None, trace=None, peak=run.peak)
    for name in NEW[1:]:
        assert spec.load_module("metrics", name).read(untraced) is None

"""BENCHMARK.json resolves to files found by name, and a cell, config,
traffic mix or metric made of new files only is picked up unchanged."""
import json
import shutil
import types

import numpy as np
import pytest

from benchlib import spec, traffic

BM = spec.load_benchmark()


def test_every_workload_resolves_to_its_files():
    for w in BM["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["arrivals"]["rate_qps"] > 0
        assert int(cell.traffic["batch"]) > 0
        spec.load_module("references", cell.config["reference"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.load_module("metrics", m["name"]).read)


def test_every_config_file_lies_under_paths_and_names_itself():
    for c in BM["configs"]:
        assert c["file"].startswith(BM["paths"][0] + "/")
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])


def test_every_roofline_metric_has_its_kernel_work():
    for m in BM["per_layer"]:
        if "_roofline" in m["name"]:
            kernel = m["name"].split("_roofline")[0]
            assert callable(spec.load_module("work", kernel).work)
            assert m["unit"] == "%"


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in BM["per_layer"]:
        for cell in m["workloads"]:
            e2e = {x["name"] for x in spec.load_cell(cell).end_to_end}
            assert m["moves"] in e2e, (m["name"], cell)


def test_new_files_alone_add_a_cell(tmp_path):
    """A new config, traffic mix and metric: new files, and entries added
    to BENCHMARK.json; no existing file under bench/ is edited."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(spec.BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    cfg = json.loads((bench / "configs" / "msmarco768-int8-device.json").read_text())
    cfg["name"] = "tiny-int8"
    (bench / "configs" / "tiny-int8.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "slow-poisson.json").write_text(json.dumps(
        {"arrivals": {"kind": "poisson", "rate_qps": 5},
         "batch": 8, "queries": {"kind": "fresh"}}))
    (bench / "metrics" / "answered_share.py").write_text(
        "def read(run):\n    return run.share\n")
    bm = json.loads(json.dumps(BM))
    bm["configs"].append({"name": "tiny-int8", "source": "test",
                          "file": "bench/configs/tiny-int8.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-slow", "config": "tiny-int8",
                            "traffic": "slow-poisson", "chips": 1, "why": "t"})
    bm["per_layer"].append({"name": "answered_share", "unit": "%",
                            "better": "higher", "source": "host_clock",
                            "layer": "load generator", "moves": "p99_ms",
                            "workloads": ["tiny-slow"]})
    for m in bm["end_to_end"]:  # the new cell reports what int8dev-poisson does
        if "int8dev-poisson" in m.get("workloads", ()):
            m["workloads"].append("tiny-slow")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = spec.load_cell("tiny-slow", root=root, bench=bench)
    assert cell.config["name"] == "tiny-int8"
    assert cell.traffic["arrivals"]["rate_qps"] == 5
    assert [m["name"] for m in cell.per_layer] == ["answered_share"]
    assert {m["name"] for m in cell.end_to_end} == {
        m["name"] for m in spec.load_cell("int8dev-poisson").end_to_end}
    reader = spec.load_module("metrics", "answered_share", bench)
    assert reader.read(types.SimpleNamespace(share=42.0)) == 42.0
    # The existing cells still resolve as before.
    assert spec.load_cell("int8dev-poisson", root=root, bench=bench).config == \
        spec.load_cell("int8dev-poisson").config


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_module("metrics", "no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.check_name("has space")


def test_poisson_arrivals_offer_the_same_work_for_every_seed():
    a = {"kind": "poisson", "rate_qps": 400}
    t1 = traffic.arrival_times(a, 10.0, np.random.default_rng(1))
    t2 = traffic.arrival_times(a, 10.0, np.random.default_rng(2**31 + 7))
    assert len(t1) == len(t2) == 4000
    assert np.all(np.diff(t1) > 0)
    # Same set of gaps in another order; mean rate as asked.
    def gaps(t):
        return np.sort(np.r_[t[0], np.diff(t)])

    assert np.allclose(gaps(t1), gaps(t2), rtol=0, atol=1e-9)
    assert t1[-1] == pytest.approx(10.0, rel=0.01)
    assert not np.array_equal(t1, t2)
    again = traffic.arrival_times(a, 10.0, np.random.default_rng(1))
    assert np.array_equal(t1, again)


def test_fresh_queries_never_repeat():
    rng = np.random.default_rng(0)
    rows = traffic.query_rows({"kind": "fresh"}, 500, 1000, rng)
    assert len(set(rows.tolist())) == 500 and rows.max() < 1000
    with pytest.raises(traffic.TrafficError):
        traffic.query_rows({"kind": "fresh"}, 2000, 1000, rng)
    with pytest.raises(traffic.TrafficError):
        traffic.query_rows({"kind": "zipf_pool"}, 10, 1000, rng)
    with pytest.raises(traffic.TrafficError):
        traffic.arrival_times({"kind": "burst", "rate_qps": 5}, 1.0, rng)

"""``correct`` at a size a test run holds, on the CPU, with the harness's
look for a chip skipped and everything else as a run does it: a sound run
comes out correct; the control (the reference in bfloat16 in the program's
place) and each fault planted in the timed path come out not correct.

Faults where the answer is produced (the host-tier rescore of the int4
configuration, the device-tier search of the int8 one), which
``score_err`` and the rules on each answer catch:
  half_batch  the second half of every batch's requests gets the answers
              of the first half;
  altered     one id of every answer is changed, its score kept;
  unchanged   every batch after the first returns the previous batch's
              answers, as a step that leaves its state as it was.

Faults ahead of the rescore (``benchlib/faults.py``: routing, sketch
pre-filter, code pass), which return real passages with true scores and
which the recall floor catches.
"""
import dataclasses
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

import control
from benchlib import faults, runner, spec
from benchlib import system as system_lib

SEED = 2**31 + 77
# cell, the program function that produces its answers, where its queries are
CELLS = {"int4": ("int4host-poisson", "host_rescore", 3),
         "int8": ("int8dev-poisson", "search_lider", 1)}


def tiny(cell) -> spec.Cell:
    if isinstance(cell, str):
        cell = spec.load_cell(cell)
    cfg = json.loads(json.dumps(cell.config))
    cfg.update(corpus_size=4096, dim=64)
    cfg["lider"].update(n_clusters=16, n_probe=4, kmeans_iters=3, capacity=None)
    tr = json.loads(json.dumps(cell.traffic))
    tr["arrivals"]["rate_qps"] = 150
    return dataclasses.replace(cell, config=cfg, traffic=tr)


@pytest.fixture(scope="module")
def built():
    """One build per configuration, served again by every run below."""
    cache = {}

    def get(cell):
        def make(config, batch, corpus, key):
            if cell.name not in cache:
                cache[cell.name] = system_lib.build(config, batch, corpus, key)
            return cache[cell.name]
        return make
    return get


def run(cell, make_system):
    return runner.run(cell, SEED, 1.0, False, t_start=time.perf_counter(),
                      require_chip=False, make_system=make_system)


def fault(kind):
    last = {}

    def change(out, q):
        ids, scores = np.asarray(out.ids), np.asarray(out.scores)
        if kind == "half_batch":
            n = int(np.any(np.asarray(q) != 0, axis=1).sum())  # not padding
            h = (n + 1) // 2
            ids, scores = ids.copy(), scores.copy()
            ids[h:n], scores[h:n] = ids[:n - h], scores[:n - h]
        elif kind == "altered":
            ids = ids.copy()
            ids[:, 0] = (ids[:, 0] + 1) % 4096
        elif kind == "unchanged":
            prev = last.get("out")
            last["out"] = (ids, scores)
            if prev is not None:
                ids, scores = prev
        return type(out)(ids=jnp.asarray(ids), scores=jnp.asarray(scores))
    return change


@pytest.mark.parametrize("which", sorted(CELLS))
def test_sound_run_is_correct(which, built):
    cell = tiny(CELLS[which][0])
    out = run(cell, built(cell))
    assert out["correct"], out["limits"]
    assert out["failed"] == 0 and out["attempted"] == 150
    assert out["limits"]["score_err"]["value"] < 1e-6
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert {"p50_ms", "recall_at_10", "setup_s"} <= set(out["metrics"])
    assert 0.2 < out["metrics"]["recall_at_10"]["value"] <= 1.0
    recall = out["limits"]["recall_at_10_min"]
    assert recall["value"] >= recall["limit"] == cell.config["check"]["recall_floor"]


@pytest.mark.parametrize("which", sorted(CELLS))
def test_control_is_not_correct(which):
    cell = tiny(CELLS[which][0])
    ref = spec.load_module("references", cell.config["reference"])
    out = run(cell, control.control_system(ref))
    assert not out["correct"]
    err = out["limits"]["score_err"]
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("kind", ["half_batch", "altered", "unchanged"])
@pytest.mark.parametrize("which", sorted(CELLS))
def test_fault_is_not_correct(which, kind, built, monkeypatch):
    from repro.core import lider

    name, fn, q_arg = CELLS[which]
    cell = tiny(name)
    orig = getattr(lider, fn)
    change = fault(kind)

    def broken(*a, **kw):
        return change(orig(*a, **kw), a[q_arg])

    broken._cache_size = getattr(orig, "_cache_size", lambda: 0)
    monkeypatch.setattr(lider, fn, broken)
    out = run(cell, built(cell))
    assert not out["correct"], (kind, out["limits"])
    assert out["failed"] > 0


@pytest.mark.parametrize("which,kind", [
    ("int4", "routing"), ("int4", "sketch"), ("int4", "codes"),
    ("int8", "routing"), ("int8", "codes")])
def test_planted_fault_is_not_correct(which, kind, built):
    cell = tiny(CELLS[which][0])
    with faults.planted(kind):
        out = run(cell, built(cell))
    assert not out["correct"], (kind, out["limits"])
    assert out["failed"] == 0  # real passages, true scores: only recall tells
    assert out["limits"]["score_err"]["value"] < 1e-6
    recall = out["limits"]["recall_at_10_min"]
    assert recall["value"] < recall["limit"]

"""Spans and stage scopes of the serving path: the search stages named in
the served jits' op metadata, the engine's per-batch spans on the
profiler's host plane, and the counters those spans feed."""
import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import faults
from repro.core import lider
from repro.serving import RetrievalEngine, make_backend
from repro.serving.spans import Span

CFG = lider.LiderConfig(
    n_clusters=32, n_probe=8, n_arrays=4, n_leaves=4, kmeans_iters=10,
    storage_dtype="int8",
)
K, BS = 10, 16
ENGINE_SPANS = ("engine.take_batch", "engine.h2d", "engine.dispatch",
                "engine.wait", "engine.d2h", "engine.record")


@pytest.fixture(scope="module")
def banks(corpus):
    """An int8 device-tier index and an int4 host-tier one."""
    x, q, _ = corpus
    dev = lider.build_lider(jax.random.PRNGKey(0), x, CFG)
    host = lider.set_rescore_tier(
        lider.build_lider(
            jax.random.PRNGKey(0), x,
            dataclasses.replace(CFG, storage_dtype="int4"),
        ),
        "host",
    )
    return x, q, dev, host


# ---------------------------------------------------------------------------
# Stage scopes in the lowered search
# ---------------------------------------------------------------------------


def _lowered(banks, which):
    x, q, dev, host = banks
    qs = q[:BS]
    if which == "device":
        lo = lider._search_lider_device.lower(dev, qs, k=K, n_probe=8, r0=8)
    elif which == "host_first_pass":
        lo = lider.host_first_pass.lower(
            host, qs, k=K, n_probe=8, r0=8, sketch_factor=2
        )
    else:
        fetched = jnp.zeros((BS, 4 * K, x.shape[1]), jnp.float32)
        rows = jnp.zeros((BS, 4 * K), jnp.int32)
        lo = lider.host_rescore.lower(host.bank.gids, fetched, rows, qs, k=K)
    return lo.as_text(debug_info=True)


@pytest.mark.parametrize("which,scopes", [
    ("device", ("lider.route", "lider.candidates", "lider.code_pass",
                "lider.rescore")),
    ("host_first_pass", ("lider.route", "lider.candidates", "lider.sketch",
                         "lider.code_pass")),
    ("host_rescore", ("lider.rescore",)),
])
def test_served_jits_name_their_stages(banks, which, scopes):
    text = _lowered(banks, which)
    for scope in scopes:
        assert f"/{scope}/" in text, scope
    # Stages never nest: no op's name holds two of them.
    stages = ("lider.route", "lider.candidates", "lider.sketch",
              "lider.code_pass", "lider.rescore")
    for line in text.splitlines():
        assert sum(f"/{s}/" in line for s in stages) <= 1, line


def test_host_first_pass_skips_the_sketch_scope_without_a_sketch(banks):
    """The sketch scope appears only where the pre-filter runs."""
    x, q, _, host = banks
    text = lider.host_first_pass.lower(
        host, q[:BS], k=K, n_probe=8, r0=8
    ).as_text(debug_info=True)
    assert "/lider.code_pass/" in text and "/lider.sketch/" not in text


# ---------------------------------------------------------------------------
# The timing helper
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Stats:
    x_us: float = 0.0


def test_span_adds_its_microseconds_and_nothing_on_error():
    st = _Stats()
    with Span("engine.test", batch=3, stats=st, counter="x_us") as sp:
        time.sleep(0.01)
    assert sp.s >= 0.01 and st.x_us == pytest.approx(sp.s * 1e6)
    with pytest.raises(RuntimeError):
        with Span("engine.test", stats=st, counter="x_us"):
            raise RuntimeError("fetch failed")
    assert st.x_us == pytest.approx(sp.s * 1e6)
    with Span() as bare:  # a plain timer: no annotation, no counter
        pass
    assert bare.s >= 0.0 and bare.t0 > 0.0


# ---------------------------------------------------------------------------
# Engine counters
# ---------------------------------------------------------------------------


def _engine(banks, tier, **kw):
    x, q, dev, host = banks
    if tier == "flat":
        search = make_backend("flat", None, x)
        eng = RetrievalEngine(search, batch_size=BS, k=K, dim=x.shape[1], **kw)
    else:
        search = make_backend("lider", None, updatable=True, n_probe=8, r0=8)
        eng = RetrievalEngine(
            search, batch_size=BS, k=K, dim=x.shape[1],
            params=dev if tier == "device" else host, **kw,
        )
    eng.warmup()
    return eng


def _serve(eng, q, n):
    rids = [eng.submit(v) for v in np.asarray(q)[:n]]
    eng.drain()
    return [eng.result(r) for r in rids]


@pytest.mark.parametrize("tier", ["flat", "device", "host"])
def test_queue_wait_and_service_sum_to_latency(banks, tier):
    eng = _engine(banks, tier)
    answers = _serve(eng, banks[1], 40)
    s = eng.stats
    assert s.queue_wait_us > 0 and s.service_us > 0
    total_s = sum(a.latency_s for a in answers)
    # Per request, queue wait + service is the answer's latency; summed
    # over 40 requests the only gap is floating-point rounding.
    assert (s.queue_wait_us + s.service_us) / 1e6 == pytest.approx(
        total_s, abs=1e-6
    )
    assert s.service_us / 1e6 <= total_s


@pytest.mark.parametrize("tier", ["flat", "device", "host"])
def test_h2d_bytes_per_batch(banks, tier):
    eng = _engine(banks, tier)
    _serve(eng, banks[1], 40)
    s = eng.stats
    d = banks[0].shape[1]
    per_batch = BS * d * 4
    if tier == "host":
        per_batch += BS * 4 * K * d * 4  # the fetched k' = 4k rows
    assert s.n_batches == 3
    assert s.h2d_bytes == s.n_batches * per_batch
    assert s.h2d_us > 0 and s.n_d2h == s.n_batches and s.d2h_us > 0


@pytest.mark.parametrize("tier", ["flat", "host"])
def test_d2h_delay_lands_in_d2h_not_in_total_time(banks, tier):
    delay = 0.1
    plan = faults.FaultPlan([faults.FaultSpec(
        faults.D2H, mode="delay", delay_s=delay, times=(0, 1, 2),
    )])
    eng = _engine(banks, tier, fault_plan=plan)
    _serve(eng, banks[1], 40)
    s = eng.stats
    assert s.n_d2h == 3
    assert s.d2h_us >= 3 * delay * 1e6
    assert s.total_time_s < 3 * delay


# ---------------------------------------------------------------------------
# Spans on the profiler's host plane
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tier", ["flat", "host"])
def test_engine_spans_reach_the_profiler(banks, tier, tmp_path):
    from jax.profiler import ProfileData

    eng = _engine(banks, tier)
    rids = [eng.submit(v) for v in np.asarray(banks[1])[:40]]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    assert all(eng.result(r) is not None for r in rids)
    path = glob.glob(os.path.join(tmp_path, "plugins/profile/*/*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    # Every span carries its batch's dispatch sequence
                    # number, so the spans of one batch share an id.
                    batch = [v for k, v in e.stats if k == "batch"]
                    assert len(batch) == 1, e.name
                    spans.setdefault(e.name, set()).update(batch)
    expected = ENGINE_SPANS + (
        ("engine.host_fetch",) if tier == "host" else ())
    assert spans == {name: {0, 1, 2} for name in expected}

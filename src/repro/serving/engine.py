"""Batched retrieval serving engine.

Wraps an index backend (LIDER or any baseline) behind one API:
``submit`` queues requests, ``drain`` executes them in batches — the
latency-vs-throughput batching knob real serving stacks tune. AQT
(average query time, the paper's efficiency metric) is measured here.

Execution is split from scheduling (DESIGN.md §Serving front end): a
:class:`~.scheduler.Scheduler` decides admission, per-tenant fairness,
result-cache hits, and the batch size of each dispatch; the engine owns
the execution core (:meth:`RetrievalEngine._execute_batch`, tier-
dispatched), the double-buffered host-tier pipeline, the degradation
ladder, and transactional updates. The default ``SchedulerConfig``
reproduces the legacy fixed-batch FIFO engine byte-for-byte.

Backends share the signature ``search(queries (B, d), k) -> TopK``; an
*updatable* LIDER backend takes ``search(params, queries, k)`` and the engine
owns the served params so ``apply_updates`` can swap them between batches
(checkpointed serving + online upsert/delete — DESIGN.md §Index lifecycle).
"""
from __future__ import annotations

import collections
import dataclasses
import random
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults
from ..core import lider as lider_lib
from ..core.baselines import (
    flat_search,
    ivfpq_search,
    mplsh_search,
    pq_search,
    sklsh_search,
)
from ..core.core_model import TopK
from .scheduler import DEFAULT_TENANT, Request, Scheduler, SchedulerConfig
from .spans import Span


@dataclasses.dataclass
class EngineStats:
    n_queries: int = 0
    n_batches: int = 0
    total_time_s: float = 0.0
    n_padded: int = 0  # pad slots executed for partial batches
    # Adaptive probe pruning (DESIGN.md §Adaptive speed-quality control
    # plane): probes routed by layer 1 but masked by the margin rule. The
    # per-batch trace is a bounded deque (newest batches) — a long-running
    # server must not grow per-batch state without bound; the lifetime
    # aggregate lives in the two counters.
    n_probes_total: int = 0
    n_probes_pruned: int = 0
    batch_pruned_fraction: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=256)
    )
    n_results_evicted: int = 0  # results dropped by the bounded results map
    # Tiered serving (DESIGN.md §Tiered embedding store): host-side exact-row
    # fetch accounting. A fetch is "overlapped" when the next batch's
    # compressed first pass was already dispatched to the device before the
    # fetch ran — the double-buffered pipeline's payoff condition.
    host_fetch_us: float = 0.0
    host_fetch_bytes: int = 0  # bytes of the rows those fetches returned
    n_host_fetches: int = 0
    n_overlapped_fetches: int = 0
    # Fault tolerance (DESIGN.md §Failure model): update transactions,
    # host-fetch retry/degrade, admission control, deadline accounting.
    n_update_rollbacks: int = 0  # failed apply_updates rolled back
    n_fetch_retries: int = 0  # host fetches retried after a failure
    n_fetch_failures: int = 0  # batches whose fetch exhausted all retries
    n_degraded: int = 0  # queries answered compressed-only (degraded=True)
    n_shed: int = 0  # requests rejected by admission control
    n_deadline_misses: int = 0  # answered, but past the per-request deadline
    n_rung_steps: int = 0  # degradation-ladder step-downs
    # Front-end scheduler counters (DESIGN.md §Serving front end). Cache
    # hits count in n_queries (they are answered traffic) but add zero
    # device time. Like batch_pruned_fraction above, the per-batch /
    # per-request traces are bounded deques: lifetime aggregates live in
    # counters, recent windows in deques — nothing grows with uptime.
    n_cache_hits: int = 0
    n_cache_misses: int = 0  # admitted-to-queue (executed on device)
    batch_size_trace: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=256)
    )
    recent_latency_s: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=1024)
    )
    # Per-batch spans (serving/spans.py): host-to-device transfers (query
    # batches, host-tier fetched rows) and the answers' device-to-host
    # conversion. Per request, summed at the batch boundary: queue wait
    # (submit to its batch's dispatch) and service (dispatch to answer
    # recorded); for a device-answered request the two sum to latency_s.
    h2d_us: float = 0.0
    h2d_bytes: int = 0
    d2h_us: float = 0.0
    n_d2h: int = 0
    queue_wait_us: float = 0.0
    service_us: float = 0.0

    @property
    def aqt(self) -> float:
        return self.total_time_s / max(self.n_queries, 1)

    @property
    def cache_hit_rate(self) -> float:
        """Fraction of answered (non-shed) requests served from the cache."""
        return self.n_cache_hits / max(
            self.n_cache_hits + self.n_cache_misses, 1
        )

    def latency_quantile(self, q: float) -> float:
        """Latency quantile (e.g. 0.5 / 0.99) over the recent window."""
        if not self.recent_latency_s:
            return 0.0
        return float(np.quantile(np.asarray(self.recent_latency_s), q))

    @property
    def overlap_fraction(self) -> float:
        """Fraction of host fetches that ran under a dispatched next batch."""
        return self.n_overlapped_fetches / max(self.n_host_fetches, 1)

    @property
    def padding_fraction(self) -> float:
        """Fraction of executed batch slots that were padding (wasted work)."""
        return self.n_padded / max(self.n_queries + self.n_padded, 1)

    @property
    def pruned_probe_fraction(self) -> float:
        """Fraction of routed probes the margin rule pruned (all batches)."""
        return self.n_probes_pruned / max(self.n_probes_total, 1)


class QueryResult:
    """One answered request. Unpacks like the legacy ``(ids, scores)`` pair
    (``ids, scores = engine.result(rid)`` / ``engine.result(rid)[0]``) and
    additionally carries the fault-tolerance metadata: ``degraded`` is True
    when the answer came from the compressed-only fallback (no exact
    rescore), ``rung`` is the degradation-ladder rung it was served at
    (0 = nominal), ``latency_s`` is submit-to-answer wall time, ``cached``
    marks answers served from the scheduler's result cache (bit-identical
    to a fresh search at the same generation and rung). ``generation`` is
    the engine generation the answer was computed at — the replica
    router's wrong-generation guard (DESIGN.md §Replica fabric) — and
    ``replica`` names the serving replica when a router dispatched it."""

    __slots__ = (
        "ids", "scores", "degraded", "rung", "latency_s", "cached",
        "generation", "replica",
    )

    def __init__(
        self, ids, scores, *, degraded=False, rung=0, latency_s=0.0,
        cached=False, generation=None, replica=None,
    ):
        self.ids = ids
        self.scores = scores
        self.degraded = degraded
        self.rung = rung
        self.latency_s = latency_s
        self.cached = cached
        self.generation = generation
        self.replica = replica

    def __iter__(self):
        return iter((self.ids, self.scores))

    def __getitem__(self, i):
        return (self.ids, self.scores)[i]

    def __len__(self):
        return 2

    def __repr__(self):
        tag = f", degraded rung={self.rung}" if self.degraded else ""
        return f"QueryResult(k={len(np.asarray(self.ids))}{tag})"


@dataclasses.dataclass(frozen=True)
class Shed:
    """Structured rejection: queue-cap admission control refused the
    request instead of growing the queue without bound. Returned by
    ``result(rid)`` for shed rids."""

    rid: int
    reason: str = "queue_full"


class _EvictedType:
    """Singleton sentinel: the answer existed but was evicted by the
    bounded results map. Falsy, so ``if engine.result(rid):`` treats it
    like a missing answer, while ``is EVICTED`` distinguishes it from a
    never-submitted/already-collected rid (``None``)."""

    def __repr__(self):
        return "EVICTED"

    def __bool__(self):
        return False


EVICTED = _EvictedType()


@dataclasses.dataclass
class _PendingBatch:
    """One stage1-dispatched batch in the host-tier pipeline. ``rung``/
    ``bs`` are captured at dispatch (the live rung may step before the
    batch finishes); ``seq`` is its dispatch sequence number (the ``batch``
    of its spans) and ``t_disp`` its dispatch time; ``retry_at`` is the
    earliest wall time a failed fetch may be retried (None = ready now);
    ``overlap_armed`` is set when a later batch's stage 1 was dispatched
    under this batch's fetch."""

    chunk: list
    bs: int
    q: jnp.ndarray
    prov: object
    pruned: object
    rung: int
    seq: int
    t_disp: float
    attempts: int = 0
    retry_at: Optional[float] = None
    overlap_armed: bool = False
    blocked: bool = False


@dataclasses.dataclass(frozen=True)
class DegradePolicy:
    """Fault-tolerance policy for :class:`RetrievalEngine`.

    ``ladder`` is a sequence of operating-point override dicts (cheapest
    last; keys other than ``lider.OperatingPoint`` fields, such as the
    modeled ``expected_recall`` floor, are report metadata the engine
    ignores), typically from ``tuning.pareto.degradation_ladder``; under
    deadline pressure or repeated host-fetch failure the engine steps down
    one rung at a time, and past the last rung (or when a batch's fetch
    exhausts its retries) answers compressed-only with ``degraded=True``.
    ``deadline_s`` is the per-request answer deadline driving both the
    rung controller (queue age thresholds as fractions of the deadline)
    and ``n_deadline_misses``. ``max_queue`` enables admission control
    (:class:`Shed`). Backoff jitter is seeded — replays deterministically.
    """

    ladder: tuple = ()
    deadline_s: Optional[float] = None
    degrade_age_fraction: float = 0.5
    recover_age_fraction: float = 0.25
    fetch_retries: int = 2
    fetch_backoff_s: float = 0.002
    fetch_backoff_mult: float = 2.0
    max_queue: Optional[int] = None
    seed: int = 0


# Searchable knobs each backend accepts; anything else in **kw is a typo and
# raises instead of being silently ignored. LIDER takes the fields of its
# ``OperatingPoint``. All probing backends take the same ``n_probe``
# spelling (mplsh's search fn calls it n_probes internally).
_POINT_FIELDS = frozenset(
    f.name for f in dataclasses.fields(lider_lib.OperatingPoint)
)
_BACKEND_KWARGS: dict[str, frozenset[str]] = {
    "lider": _POINT_FIELDS,
    "flat": frozenset(),
    "pq": frozenset(),
    "ivfpq": frozenset({"n_probe"}),
    "sklsh": frozenset(),
    "mplsh": frozenset({"n_probe"}),
}


def make_backend(
    kind: str,
    index,
    embs: jnp.ndarray | None = None,
    *,
    updatable: bool = False,
    **kw,
) -> Callable:
    """Uniform search closure over any index type.

    ``updatable=True`` (LIDER only) returns ``search(params, q, k)`` instead
    of closing over the index — pass the params to ``RetrievalEngine`` so
    ``apply_updates`` can swap them between batches.
    """
    if kind not in _BACKEND_KWARGS:
        raise ValueError(
            f"unknown backend {kind!r}; expected one of "
            f"{sorted(_BACKEND_KWARGS)}"
        )
    unknown = set(kw) - _BACKEND_KWARGS[kind]
    if unknown:
        allowed = sorted(_BACKEND_KWARGS[kind]) or "none"
        raise TypeError(
            f"backend {kind!r} got unexpected kwargs {sorted(unknown)}; "
            f"allowed: {allowed}"
        )
    if updatable and kind != "lider":
        raise ValueError(f"updatable backends require kind='lider', got {kind!r}")

    if kind == "lider":
        base = lider_lib.OperatingPoint(**kw)

        def _at(rung):
            # A degradation-ladder rung overrides fields of the base point;
            # the nominal path (rung=None) serves the base point itself.
            return dataclasses.replace(base, **rung) if rung else base

        def lider_search(params, q, k, point=None):
            # With pruning on, the search also returns the (B, P) bool mask
            # of routed-but-pruned probes; the engine folds it into
            # EngineStats (per-batch pruned-probe fraction).
            at = _at(point)
            return lider_lib.search_lider(
                params, q, k=k, with_stats=at.prune_margin is not None,
                **dataclasses.asdict(at),
            )

        lider_search.accepts_point = True

        if updatable:
            # Staged spelling of the same operating point, for host-tier
            # (rescore_tier="host") params: the engine pipelines stage1 of
            # batch i+1 over batch i's host fetch + rescore (DESIGN.md
            # §Tiered embedding store). search_lider composes the identical
            # stages serially, so results match the unpipelined call.
            def host_stage1(params, q, k, point=None):
                at = _at(point)
                prov, pruned = lider_lib.first_pass(params, q, k=k, point=at)
                # Same contract as the serial path: probe stats only when
                # the margin rule is actually configured.
                return prov, (pruned if at.prune_margin is not None else None)

            rescore = base.only("use_fused", "block_c")

            def host_stage2(params, fetched, prov_rows, q, k):
                return lider_lib.host_rescore(
                    params.bank.gids, fetched, prov_rows, q, k=k,
                    point=rescore,
                )

            lider_search.host_stage1 = host_stage1
            lider_search.host_fetch = lider_lib.host_fetch
            lider_search.host_stage2 = host_stage2
            return lider_search

        def search(q, k, point=None):
            return lider_search(index, q, k, point=point)

        search.accepts_point = True
    elif kind == "flat":
        def search(q, k):
            return flat_search(embs, q, k=k)
    elif kind == "pq":
        def search(q, k):
            return pq_search(index, q, k=k)
    elif kind == "ivfpq":
        def search(q, k):
            return ivfpq_search(index, q, k=k, n_probe=kw.get("n_probe", 8))
    elif kind == "sklsh":
        def search(q, k):
            return sklsh_search(index, embs, q, k=k)
    else:  # mplsh
        def search(q, k):
            return mplsh_search(index, embs, q, k=k, n_probes=kw.get("n_probe", 8))
    return search


class RetrievalEngine:
    """Batched serving with scheduled admission and AQT accounting.

    With ``params`` set, ``search_fn`` must take ``(params, q, k)`` and the
    engine serves whatever params it currently holds — ``apply_updates``
    swaps them atomically between batches, tracking a generation counter and
    recompiling (re-warming) only when an update grew array shapes (capacity
    growth); same-shape updates reuse the compiled search.

    ``scheduler`` (a :class:`SchedulerConfig`) configures the front end:
    per-tenant weighted-fair queues, the result cache, dynamic batch
    sizing, and SLO-driven admission. The default config is the legacy
    fixed-batch FIFO behavior exactly.
    """

    def __init__(
        self,
        search_fn: Callable,
        *,
        batch_size: int,
        k: int,
        dim: int,
        params=None,
        max_results: int = 65536,
        policy: DegradePolicy | None = None,
        fault_plan=None,
        scheduler: SchedulerConfig | None = None,
    ):
        self.search_fn = search_fn
        self.batch_size = batch_size
        self.k = k
        self.dim = dim
        self.params = params
        # Fault tolerance (DESIGN.md §Failure model): ``policy`` drives
        # retry/degrade/shed behavior; ``fault_plan`` (a faults.FaultPlan)
        # is activated around drain/apply_updates for chaos testing.
        self.policy = policy if policy is not None else DegradePolicy()
        self.fault_plan = fault_plan
        self.rung = 0  # current degradation-ladder rung (0 = nominal)
        self._rng = random.Random(self.policy.seed)  # backoff jitter
        self.generation = 0  # bumped on every apply_updates
        # The tier split (DESIGN.md §Tiered embedding store): device-tier
        # state (pytree leaves) and host-tier state (the EmbStore content)
        # change independently, and only device *shape* changes ever force a
        # recompile — a host-content-only update must not re-trace anything.
        self.device_generation = 0  # pytree leaves changed
        self.host_generation = 0  # host EmbStore content changed
        self.recompiles = 0  # bumped only when shapes changed
        self.sched_cfg = scheduler if scheduler is not None else SchedulerConfig()
        self.scheduler = Scheduler(
            self.sched_cfg,
            batch_size=batch_size,
            deadline_s=self.policy.deadline_s,
            max_queue=self.policy.max_queue,
        )
        # How many stage1-dispatched batches the host-tier pipeline keeps in
        # flight (2 = the PR 5 double buffer).
        self._pipeline_depth = 2
        # Bounded FIFO of answered (ids, scores) pairs. ``result()`` pops by
        # default, so a well-behaved client keeps this near-empty; the bound
        # is the backstop for clients that never collect (a long-running
        # server must not leak every answer it has ever produced).
        if max_results < batch_size:
            raise ValueError(
                f"max_results={max_results} must hold at least one batch "
                f"({batch_size})"
            )
        self.max_results = max_results
        self.results: collections.OrderedDict[int, object] = (
            collections.OrderedDict()
        )
        # Rids whose answers were computed but evicted by the bound above —
        # itself bounded, oldest-first, so the eviction metadata cannot
        # become the leak the bound prevents.
        self._evicted: collections.OrderedDict[int, None] = (
            collections.OrderedDict()
        )
        self.stats = EngineStats()
        self._next_id = 0
        self._seq = 0  # batches dispatched: the next batch's span id

    @property
    def _accepts_point(self) -> bool:
        return getattr(self.search_fn, "accepts_point", False)

    def _rung_point(self) -> dict | None:
        """Operating-point override for the current ladder rung (None at
        rung 0 — the nominal path takes zero extra kwargs)."""
        ladder = self.policy.ladder
        if self.rung <= 0 or not ladder or not self._accepts_point:
            return None
        raw = ladder[min(self.rung, len(ladder)) - 1]
        return {k: v for k, v in raw.items() if k in _POINT_FIELDS}

    def _search(self, q: jnp.ndarray):
        point = self._rung_point()
        if self.params is not None:
            if point is not None:
                return self.search_fn(self.params, q, self.k, point=point)
            return self.search_fn(self.params, q, self.k)
        if point is not None:
            return self.search_fn(q, self.k, point=point)
        return self.search_fn(q, self.k)

    @staticmethod
    def _split_out(out) -> tuple[TopK, jnp.ndarray | None]:
        """Backends return TopK or (TopK, pruned-probe mask)."""
        if isinstance(out, tuple) and not isinstance(out, TopK):
            return out[0], out[1]
        return out, None

    def warmup(self, *, warm_ladder: bool = True):
        """Pre-compile every reachable query-path trace: each batch size on
        the scheduler's pow2 ladder, at the nominal point and (with
        ``warm_ladder``) every degradation-ladder rung. After this, neither
        a rung step nor a dynamic batch-size choice ever re-traces on the
        query path — both ladders are bounded, so this is a bounded number
        of compiles, eaten once off the serving path."""
        saved = self.rung
        staged = self._staged_host_serving()
        try:
            for bs in self.scheduler.ladder:
                q = jnp.zeros((bs, self.dim), jnp.float32)
                rungs = [0]
                if warm_ladder and self.policy.ladder and self._accepts_point:
                    rungs += list(range(1, len(self.policy.ladder) + 1))
                for r in rungs:
                    self.rung = r
                    out, _ = self._split_out(self._search(q))
                    jax.block_until_ready(out.ids)
                    if staged:
                        # The pipelined drain runs the STAGED spelling
                        # (host_first_pass -> fetch -> host_rescore), whose
                        # stage jits are separate traces from the serial
                        # search warmed above. Warm them here too — outside
                        # any faults.activate window, so chaos-plan call
                        # counters are untouched — or the first live
                        # dispatch pays the trace on the query path.
                        prov, _ = self.search_fn.host_stage1(
                            self.params, q, self.k, point=self._rung_point()
                        )
                        fetched = self.search_fn.host_fetch(
                            self.params, prov.ids
                        )
                        out2 = self.search_fn.host_stage2(
                            self.params, jnp.asarray(fetched), prov.ids, q,
                            self.k,
                        )
                        jax.block_until_ready(out2.ids)
        finally:
            self.rung = saved

    @property
    def pending_requests(self) -> int:
        """Queued (admitted, not yet executed) request count."""
        return len(self.scheduler)

    def submit(self, query: np.ndarray, *, tenant: str = DEFAULT_TENANT) -> int:
        rid = self._next_id
        self._next_id += 1
        vec = np.asarray(query, np.float32)
        req = Request(
            rid=rid,
            query=vec,
            t_submit=time.perf_counter(),
            tenant=tenant,
            fp=self.scheduler.fingerprint(vec),
        )
        reason = self.scheduler.admit(req)
        if reason is not None:
            # Admission control: refuse now with a structured answer rather
            # than queueing work we cannot serve within the deadline.
            self.stats.n_shed += 1
            self._put_result(rid, Shed(rid=rid, reason=reason))
        return rid

    def apply_updates(self, update_fn: Callable) -> bool:
        """Transactionally swap served params to ``update_fn(params)``
        between batches.

        ``update_fn`` returns either new params or ``(new_params, stats)``
        (the ``core.update`` convention). Device-tier state is functional
        (new leaves), but lifecycle ops mutate the host ``EmbStore`` IN
        PLACE — so the store is wrapped in a transaction: if ``update_fn``
        raises, every in-place host write is rolled back (bit-identical
        table, gids, and ``version``) and the engine keeps serving the old
        generation; the exception then propagates to the updater. Commit
        happens atomically with the params swap, between batches. Returns
        True when leaf shapes changed (capacity growth) — the one case the
        compiled search must re-trace; the engine eats that recompile here,
        off the query path.
        """
        if self.params is None:
            raise ValueError(
                "engine was not built with params (make_backend(..., "
                "updatable=True) + RetrievalEngine(..., params=...))"
            )
        old_leaves = jax.tree_util.tree_leaves(self.params)
        old_store = self._host_store(self.params)
        # Capture the version BEFORE the update runs: lifecycle ops mutate
        # the store in place, so the object identity alone can't tell us
        # whether its content changed.
        old_hver = None if old_store is None else old_store.version
        # Transaction covers the concrete host table only: device leaves
        # are functional and growth is copy-on-grow (a failed grown update
        # is rolled back simply by not swapping params).
        txn_store = (
            old_store
            if old_store is not None
            and old_store.tier == "host"
            and old_store.rescore is not None
            else None
        )
        if txn_store is not None:
            txn_store.begin_txn()
        try:
            with faults.activate(self.fault_plan):
                out = update_fn(self.params)
        except Exception:
            if txn_store is not None:
                txn_store.rollback()
            self.stats.n_update_rollbacks += 1
            raise
        if txn_store is not None:
            txn_store.commit()
        new_params = out[0] if isinstance(out, tuple) else out
        new_leaves = jax.tree_util.tree_leaves(new_params)
        grew = [jnp.shape(l) for l in old_leaves] != [
            jnp.shape(l) for l in new_leaves
        ]
        device_changed = grew or any(
            a is not b for a, b in zip(old_leaves, new_leaves)
        )
        new_store = self._host_store(new_params)
        host_changed = (new_store is not old_store) or (
            new_store is not None and new_store.version != old_hver
        )
        self.params = new_params
        self.generation += 1
        if device_changed:
            self.device_generation += 1
        if host_changed:
            self.host_generation += 1
        # Cache coherence: the generation is part of every cache key, so a
        # stale hit is already impossible — clearing additionally frees the
        # dead generation's entries from the bounded capacity.
        if self.scheduler.cache is not None:
            self.scheduler.cache.clear()
        if grew:
            self.recompiles += 1
            self.warmup()
        return grew

    @staticmethod
    def _host_store(params):
        return getattr(getattr(params, "bank", None), "store", None)

    def _take_batch(self, bs: int) -> list[Request]:
        """Pop up to ``bs`` requests (weighted-fair across tenants),
        answering cache hits inline and topping the batch back up from the
        queue — repeated queries never occupy device batch slots."""
        chunk: list[Request] = []
        cache = self.scheduler.cache
        while len(chunk) < bs:
            reqs = self.scheduler.take(bs - len(chunk))
            if not reqs:
                break
            for req in reqs:
                hit = (
                    cache.get(req.fp, (self.k, self.generation, self.rung))
                    if cache is not None and req.fp is not None
                    else None
                )
                if hit is not None:
                    self._answer_cached(req, hit)
                else:
                    if cache is not None:
                        self.stats.n_cache_misses += 1
                    chunk.append(req)
        return chunk

    def _answer_cached(self, req: Request, hit) -> None:
        """Serve ``req`` from the result cache: bit-identical answer
        (same bytes, generation, and rung in the key), zero device time.
        Counts in n_queries but adds nothing to total_time_s, so cache hits
        pull AQT down exactly as they pull real latency down."""
        ids, scores = hit
        now = time.perf_counter()
        latency = now - req.t_submit
        self.stats.n_cache_hits += 1
        self.stats.n_queries += 1
        self.stats.recent_latency_s.append(latency)
        deadline = self.policy.deadline_s
        if deadline is not None and latency > deadline:
            self.stats.n_deadline_misses += 1
        self._put_result(
            req.rid,
            QueryResult(
                ids.copy(),  # clients may mutate; never hand out the
                scores.copy(),  # cached arrays themselves
                rung=self.rung,
                latency_s=latency,
                cached=True,
                # The generation is in the cache key, so a hit is always
                # at the engine's current generation.
                generation=self.generation,
            ),
        )

    def _span(self, name: str, seq: int, counter: str | None = None) -> Span:
        return Span(name, batch=seq, stats=self.stats, counter=counter)

    def _next_batch(self, chunk: list[Request]) -> tuple[int, int, float]:
        """``(bs, seq, t_disp)`` of a batch leaving the queue: the smallest
        pre-warmed batch size that holds ``chunk`` (one compiled trace per
        ladder size — dispatching ``len(chunk)`` directly would re-trace
        per distinct depth), its dispatch sequence number, its dispatch
        time."""
        bs = next(
            (b for b in self.scheduler.ladder if b >= len(chunk)),
            self.scheduler.ladder[-1],
        )
        seq = self._seq
        self._seq += 1
        return bs, seq, time.perf_counter()

    def _take_next(self) -> list[Request]:
        with self._span("engine.take_batch", self._seq):
            return self._take_batch(self.scheduler.pick_batch_size())

    def _device_batch(
        self, chunk: list[Request], bs: int, seq: int
    ) -> jnp.ndarray:
        """Fill the padded (bs, dim) device batch from ``chunk``.

        Each batch fills a host buffer of its own: the transfer of a NumPy
        array to the device may read it after the call has returned, and
        the pipelined drain fills batch i+1 while batch i is in flight, so a
        shared buffer refilled in place could hand batch i the next batch's
        queries (seen on the CPU under load).
        """
        q = np.zeros((bs, self.dim), np.float32)
        for i, req in enumerate(chunk):
            q[i] = req.query
        self.stats.h2d_bytes += q.nbytes
        with self._span("engine.h2d", seq, "h2d_us"):
            return jnp.asarray(q)

    def _put_result(self, rid: int, value) -> None:
        """Insert one answer, enforcing the results-map bound."""
        self.results[rid] = value
        while len(self.results) > self.max_results:
            old_rid, _ = self.results.popitem(last=False)  # evict oldest
            self.stats.n_results_evicted += 1
            self._evicted[old_rid] = None
            while len(self._evicted) > self.max_results:
                self._evicted.popitem(last=False)

    def _record_batch(
        self, chunk, out, pruned, *, bs, seq, t_disp, rung=None,
        degraded=False,
    ) -> float:
        """Account one completed batch and route its answers. Returns its
        seconds (the result D2H conversion included), which the AQT window
        leaves out.

        ``bs``/``rung`` are the batch size and ladder rung the batch was
        *dispatched* with — under the pipelined drain the controller may
        have stepped the live rung between dispatch and completion, and the
        recorded rung must match the operating point that actually computed
        the answer. ``seq``/``t_disp`` are its dispatch sequence number and
        time."""
        with self._span("engine.d2h", seq, "d2h_us") as d2h:
            faults.fire(faults.D2H)  # "delay" here models a slow __array__
            ids = np.asarray(out.ids)
            scores = np.asarray(out.scores)
        self.stats.n_d2h += 1
        with self._span("engine.record", seq) as rec:
            self._route_answers(
                chunk, ids, scores, pruned, bs=bs, t_disp=t_disp,
                rung=self.rung if rung is None else rung, degraded=degraded,
            )
        return d2h.s + rec.s

    def _route_answers(
        self, chunk, ids, scores, pruned, *, bs, t_disp, rung, degraded,
    ) -> None:
        n = len(chunk)
        self.stats.n_queries += n
        self.stats.n_batches += 1
        self.stats.n_padded += bs - n
        self.stats.batch_size_trace.append(bs)
        if degraded:
            self.stats.n_degraded += n
        if pruned is not None:
            # Count only the n real queries — padded rows route too, but
            # their probes are not served traffic.
            pmask = np.asarray(pruned)[:n]
            self.stats.n_probes_total += int(pmask.size)
            self.stats.n_probes_pruned += int(pmask.sum())
            self.stats.batch_pruned_fraction.append(
                float(pmask.sum()) / max(pmask.size, 1)
            )
        now = time.perf_counter()
        deadline = self.policy.deadline_s
        cache = self.scheduler.cache
        queue_wait = 0.0
        for i, req in enumerate(chunk):
            latency = now - req.t_submit
            queue_wait += t_disp - req.t_submit
            self.stats.recent_latency_s.append(latency)
            if deadline is not None and latency > deadline:
                self.stats.n_deadline_misses += 1
            self._put_result(
                req.rid,
                QueryResult(
                    ids[i],
                    scores[i],
                    degraded=degraded,
                    rung=rung,
                    latency_s=latency,
                    generation=self.generation,
                ),
            )
            # Only full-fidelity answers are cacheable: a degraded
            # (compressed-only) answer at the same key would violate the
            # bit-identical-to-fresh-search guarantee.
            if cache is not None and req.fp is not None and not degraded:
                cache.put(
                    req.fp, (self.k, self.generation, rung), ids[i], scores[i]
                )
        self.stats.queue_wait_us += queue_wait * 1e6
        self.stats.service_us += n * (now - t_disp) * 1e6

    def _staged_host_serving(self) -> bool:
        """Host-tier LIDER params + a backend exposing the staged search."""
        return (
            self.params is not None
            and getattr(self.search_fn, "host_stage1", None) is not None
            and getattr(
                getattr(self.params, "bank", None), "rescore_tier", "device"
            )
            == "host"
        )

    def _adjust_rung(self) -> None:
        """Operating-point controller, called once per dispatch.

        Two modes. Legacy (no scheduler SLO): the PR 6 deadline-pressure
        hysteresis — step down (cheaper point) when the oldest queued
        request has aged past ``degrade_age_fraction`` of the deadline,
        step back up below ``recover_age_fraction``. Frontier navigation
        (``SchedulerConfig.slo_s`` set): map the scheduler's continuous
        load signal directly onto the ladder — rung = round(load * len) —
        so the engine rides the measured speed-quality frontier instead of
        walking it one reactive step at a time. Either way every rung was
        pre-compiled in warmup."""
        pol = self.policy
        if not pol.ladder or not self._accepts_point:
            return
        if self.sched_cfg.slo_s is not None:
            load = self.scheduler.load_signal(time.perf_counter())
            target = min(int(round(load * len(pol.ladder))), len(pol.ladder))
            if target > self.rung:
                self.stats.n_rung_steps += target - self.rung
            self.rung = target
            return
        if pol.deadline_s is None:
            return
        oldest = self.scheduler.oldest_submit()
        if oldest is None:
            if self.rung > 0:
                self.rung -= 1
            return
        age = time.perf_counter() - oldest
        if age >= pol.deadline_s * pol.degrade_age_fraction:
            if self.rung < len(pol.ladder):
                self.rung += 1
                self.stats.n_rung_steps += 1
        elif age <= pol.deadline_s * pol.recover_age_fraction and self.rung > 0:
            self.rung -= 1

    def drain(self, max_dispatches: int | None = None) -> None:
        """Execute queued requests in scheduler-sized batches.

        Host-tier LIDER indexes (``rescore_tier="host"``) drain through the
        double-buffered fetch->rescore pipeline (:meth:`_drain_pipelined`);
        everything else executes serially through the same per-dispatch
        plumbing (:meth:`_execute_batch`). ``max_dispatches`` bounds the
        number of batches executed this call — the open-loop driver's
        hook: submit newly-arrived traffic, drain one dispatch, repeat.
        The engine's fault plan (chaos testing) is active for the duration
        of the drain.
        """
        with faults.activate(self.fault_plan):
            if self._staged_host_serving():
                return self._drain_pipelined(max_dispatches)
            n_disp = 0
            while len(self.scheduler):
                if max_dispatches is not None and n_disp >= max_dispatches:
                    break
                self._adjust_rung()
                chunk = self._take_next()
                if not chunk:  # everything was answered from the cache
                    continue
                n_disp += 1
                self._execute_batch(chunk)

    def execute_chunk(self, chunk: list[Request]) -> list:
        """Synchronously execute one already-admitted batch and return its
        answers in request order.

        The replica router's dispatch primitive (DESIGN.md §Replica
        fabric): the router owns admission/fairness/batching in its own
        scheduler and hands fully-formed chunks to whichever replica
        engine its health mask selects; the engine runs its normal
        execution core — serial or staged host-tier, including the
        fetch-retry/degrade ladder — and the answers are popped (never
        left in the results map, so router-assigned rids can overlap
        across replicas). The engine's fault plan stays active for the
        duration, exactly as in :meth:`drain`.
        """
        with faults.activate(self.fault_plan):
            if self._staged_host_serving():
                with Span() as whole:
                    e = self._dispatch_stage1(chunk)
                    while True:
                        if e.retry_at is not None:
                            wait = e.retry_at - time.perf_counter()
                            if wait > 0:
                                time.sleep(wait)
                        d2h_s = self._finish_host_batch(e)
                        if d2h_s is not None:
                            break
                self.stats.total_time_s += max(whole.s - d2h_s, 0.0)
            else:
                self._execute_batch(chunk)
        return [self.results.pop(r.rid) for r in chunk]

    def _execute_batch(self, chunk: list[Request]) -> None:
        """The serial execution core: pad to the smallest pre-warmed batch
        size, search, block, account."""
        bs, seq, t_disp = self._next_batch(chunk)
        q = self._device_batch(chunk, bs, seq)
        with self._span("engine.dispatch", seq) as disp:
            out, pruned = self._split_out(self._search(q))
        # Block on BOTH outputs so AQT covers all device time — blocking on
        # ids alone under-counts when scores finish later. The AQT window
        # closes HERE: D2H conversion (np.asarray) is host-side transfer
        # the paper's efficiency metric must not include.
        with self._span("engine.wait", seq) as wait:
            jax.block_until_ready((out.ids, out.scores))
        dt = disp.s + wait.s
        self.stats.total_time_s += dt
        self.scheduler.observe_service(bs, dt)
        self._record_batch(chunk, out, pruned, bs=bs, seq=seq, t_disp=t_disp)

    def _drain_pipelined(self, max_dispatches: int | None = None) -> None:
        """Double-buffered host-tier drain (§Tiered embedding store).

        Batch *i+1*'s compressed first pass is dispatched to the device
        *before* batch *i*'s provisional rows come back D2H and its exact
        rows are fetched from the host tier — so the host fetch (and the
        B·k'·d H2D of the fetched rows) hides behind device work for every
        batch but the last. The AQT window spans the whole pipelined drain
        (per-batch windows would double-count the overlapped regions) and
        still excludes the result D2H conversions, which are measured and
        subtracted.

        A batch whose host fetch fails is NOT finished in place: it is
        parked with a ``retry_at`` backoff stamp while other pending
        batches keep fetching/rescoring and new stage1 work keeps
        dispatching — a host brownout slows one batch, not the pipeline
        (the engine only sleeps when every pending batch is backing off
        and there is nothing else to do).
        """
        d2h_s = 0.0
        pending: collections.deque[_PendingBatch] = collections.deque()
        n_disp = 0
        with Span() as whole:
            while len(self.scheduler) or pending:
                may_dispatch = (
                    len(self.scheduler)
                    and len(pending) < self._pipeline_depth
                    and (max_dispatches is None or n_disp < max_dispatches)
                )
                if may_dispatch:
                    self._adjust_rung()
                    chunk = self._take_next()
                    if chunk:
                        # Async dispatch: host_stage1 returns before the
                        # device finishes, so every already-pending batch's
                        # host fetch below overlaps this compute.
                        for e in pending:
                            e.overlap_armed = True
                        pending.append(self._dispatch_stage1(chunk))
                        n_disp += 1
                    continue
                if not pending:
                    break  # queue non-empty but dispatch budget exhausted
                now = time.perf_counter()
                entry = next(
                    (
                        e
                        for e in pending
                        if e.retry_at is None or e.retry_at <= now
                    ),
                    None,
                )
                if entry is None:
                    # Every pending batch is in fetch backoff and the
                    # dispatch window is closed — nothing useful to
                    # overlap; sleep to the earliest retry stamp.
                    wait = min(e.retry_at for e in pending) - now
                    if wait > 0:
                        time.sleep(wait)
                    continue
                finished_d2h = self._finish_host_batch(entry)
                if finished_d2h is not None:
                    pending.remove(entry)
                    d2h_s += finished_d2h
        self.stats.total_time_s += max(whole.s - d2h_s, 0.0)

    def _dispatch_stage1(self, chunk: list[Request]) -> "_PendingBatch":
        """Pad + dispatch the compressed first pass; capture the operating
        point (rung) the batch is computed with so its answers are recorded
        against that point even if the controller steps the live rung
        before the batch completes."""
        bs, seq, t_disp = self._next_batch(chunk)
        q = self._device_batch(chunk, bs, seq)
        with self._span("engine.dispatch", seq) as disp:
            prov, pruned = self.search_fn.host_stage1(
                self.params, q, self.k, point=self._rung_point()
            )
        self.scheduler.observe_service(bs, disp.s)
        return _PendingBatch(
            chunk=chunk, bs=bs, q=q, prov=prov, pruned=pruned, rung=self.rung,
            seq=seq, t_disp=t_disp,
        )

    def _finish_host_batch(self, e: "_PendingBatch") -> float | None:
        """Fetch + rescore one stage1-dispatched batch. Returns the result
        D2H conversion seconds (excluded from the AQT window), or None when
        the fetch failed and the batch was parked for a backoff retry.

        Under an active ``fault_plan``, a host fetch that exhausts all its
        retries does NOT abort the drain: the batch is answered
        compressed-only from its provisional top-k' (``degraded=True``) and
        the rung controller steps down one rung for subsequent batches.
        Backoff is exponential with deterministic (seeded) jitter so chaos
        runs replay identically. Without a plan the fetch error raises."""
        pol = self.policy
        if not e.blocked:
            # Close the device wait BEFORE the fetch span: np.asarray(prov)
            # inside host_fetch would otherwise block on the batch's first
            # pass and charge device compute to the host-fetch stat.
            with self._span("engine.wait", e.seq):
                jax.block_until_ready(e.prov)
            e.blocked = True
        try:
            with self._span("engine.host_fetch", e.seq, "host_fetch_us"):
                fetched = self.search_fn.host_fetch(self.params, e.prov.ids)
                self.stats.host_fetch_bytes += fetched.nbytes
        except Exception:
            # Retry and compressed-only answers are the chaos-tested
            # recovery path: without an injected-fault plan a fetch error
            # is a real fault, and it propagates instead of turning into a
            # degraded answer.
            if self.fault_plan is None:
                raise
            e.attempts += 1
            if e.attempts > pol.fetch_retries:
                self.stats.n_fetch_failures += 1
                return self._record_degraded(e)
            self.stats.n_fetch_retries += 1
            delay = pol.fetch_backoff_s * (
                pol.fetch_backoff_mult ** (e.attempts - 1)
            )
            delay *= 1.0 + self._rng.random()
            e.retry_at = time.perf_counter() + delay
            return None  # parked; the drain loop keeps other batches moving
        self.stats.n_host_fetches += 1
        if e.overlap_armed:
            self.stats.n_overlapped_fetches += 1
        self.stats.h2d_bytes += fetched.nbytes
        with self._span("engine.h2d", e.seq, "h2d_us"):
            fetched = jnp.asarray(fetched)
        with self._span("engine.dispatch", e.seq):
            out = self.search_fn.host_stage2(
                self.params, fetched, e.prov.ids, e.q, self.k
            )
        with self._span("engine.wait", e.seq):
            jax.block_until_ready((out.ids, out.scores))
        return self._record_batch(
            e.chunk, out, e.pruned, bs=e.bs, seq=e.seq, t_disp=e.t_disp,
            rung=e.rung,
        )

    def _record_degraded(self, e: "_PendingBatch") -> float:
        """Answer a fetch-exhausted batch compressed-only: stage 1 already
        holds the compressed-domain top-k' — no fetch, no exact rescore
        (DESIGN.md §Failure model, last ladder rung)."""
        if self.policy.ladder and self.rung < len(self.policy.ladder):
            self.rung += 1
            self.stats.n_rung_steps += 1
        with self._span("engine.dispatch", e.seq):
            out = lider_lib.compressed_only_topk(
                self.params.bank.gids, e.prov, k=self.k
            )
        with self._span("engine.wait", e.seq):
            jax.block_until_ready((out.ids, out.scores))
        return self._record_batch(
            e.chunk, out, e.pruned, bs=e.bs, seq=e.seq, t_disp=e.t_disp,
            rung=e.rung, degraded=True,
        )

    def result(self, rid: int, *, keep: bool = False):
        """Fetch (and by default release) the answer for ``rid``.

        Popping on read is what keeps a long-running server's memory flat;
        ``keep=True`` leaves the entry in the map (it then stays until
        re-read or evicted by the ``max_results`` bound). Return values:
        a :class:`QueryResult` (unpacks as ``(ids, scores)``), a
        :class:`Shed` for admission-control rejections, the falsy
        :data:`EVICTED` sentinel when the answer existed but was evicted by
        the ``max_results`` bound, or ``None`` for never-submitted /
        already-collected ids.
        """
        out = self.results.get(rid) if keep else self.results.pop(rid, None)
        if out is not None:
            return out
        if rid in self._evicted:
            return EVICTED
        return None

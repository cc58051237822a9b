"""The system under test, driven through its normal entry points:
``build_lider`` -> ``make_backend("lider", ...)`` -> ``RetrievalEngine``.

What a configuration file sets goes to the program unchanged: ``lider`` to
``LiderConfig``, ``backend`` to ``make_backend``, ``engine`` to
``RetrievalEngine`` and ``scheduler`` to ``SchedulerConfig``. The program is
imported from ``<checkout>/src`` only when a run builds it.
"""
from __future__ import annotations

import dataclasses
import sys

import jax

from .spec import ROOT


class ProgramMissing(RuntimeError):
    """``src/repro`` is not in the checkout."""


def import_program():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise ProgramMissing(f"the system under test is not at {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro import serving
    from repro.core import lider

    return serving, lider


@dataclasses.dataclass
class System:
    engine: object
    params: object
    retraces: object  # () -> compiled query-path traces so far

    def stats(self) -> dict:
        """The engine's counters (``EngineStats`` numbers) as they stand."""
        st = self.engine.stats
        return {f.name: getattr(st, f.name) for f in dataclasses.fields(st)
                if isinstance(getattr(st, f.name), (int, float))}


def build(config: dict, batch: int, corpus: jax.Array, build_key) -> System:
    """Build the index over ``corpus`` and a warmed engine at ``batch``."""
    serving, lider = import_program()
    lcfg = lider.LiderConfig(**config["lider"])
    params = lider.build_lider(build_key, corpus, lcfg)
    jax.block_until_ready(params)
    point = dict(n_probe=lcfg.n_probe, r0=lcfg.r0,
                 rescore_factor=lcfg.rescore_factor)
    backend = serving.make_backend("lider", None, updatable=True, **point,
                                   **config.get("backend", {}))
    sched = config.get("scheduler")
    engine = serving.RetrievalEngine(
        backend, batch_size=batch, k=int(config["k"]), dim=int(config["dim"]),
        params=params,
        scheduler=serving.SchedulerConfig(**sched) if sched else None,
        **config.get("engine", {}),
    )
    engine.warmup()
    return System(engine=engine, params=params,
                  retraces=lider.query_path_cache_size)


def engine_over(search_fn, config: dict, batch: int) -> System:
    """An engine serving ``search_fn(q, k)`` in the program's place (the
    control: the reference at a lower precision)."""
    serving, _ = import_program()
    engine = serving.RetrievalEngine(search_fn, batch_size=batch,
                                     k=int(config["k"]), dim=int(config["dim"]))
    engine.warmup()
    return System(engine=engine, params=None, retraces=lambda: 0)

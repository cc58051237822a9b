"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, traffic mix, metric, kernel
or reference sits in a file of its own, found by its name, so a later cell,
configuration or metric is added as new files and no existing file changes:

    configs/<config>.json     one deployment (``file`` in BENCHMARK.json)
    traffic/<traffic>.json    one traffic mix: arrivals, batch, queries
    metrics/<metric>.py       one metric: ``read(run) -> float | None``
    work/<kernel>.py          ops and bytes of one kernel call, from shapes
    references/<name>.py      the plain reference a configuration names
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")


class SpecError(Exception):
    """A cell, file or name that the benchmark cannot resolve."""


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise SpecError(f"bad name {name!r}: 1-64 of A-Z a-z 0-9 _ . -")
    return name


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(kind: str, name: str, bench: Path = BENCH):
    """Import ``<bench>/<kind>/<name>.py`` (names may hold dots)."""
    path = bench / kind / f"{check_name(name)}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} file for {name!r} at {path}")
    mod_name = "bench_" + re.sub(r"\W", "_", f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_applies(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    """A metric with ``workloads`` is read in those cells; a per-layer
    metric without it in every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # metric entries of BENCHMARK.json that this cell reports
    per_layer: tuple
    bench: Path = BENCH

    def metrics(self, trace: bool) -> tuple:
        return self.per_layer if trace else self.end_to_end


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def load_cell(workload: str, root: Path = ROOT, bench: Path = BENCH) -> Cell:
    bm = load_benchmark(root)
    check_name(workload)
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config {w['config']!r}")
    cfg_path = (root / configs[w["config"]]["file"]).resolve()
    if bench.resolve() not in cfg_path.parents:
        raise SpecError(f"config file {cfg_path} lies outside {bench}")
    config = load_json(cfg_path)
    traffic = load_json(bench / "traffic" / f"{check_name(w['traffic'])}.json")
    e2e = tuple(m for m in bm["end_to_end"] if metric_applies(m, workload, set()))
    names = {m["name"] for m in e2e}
    per = tuple(m for m in bm["per_layer"] if metric_applies(m, workload, names))
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per, bench=bench)

"""Peaks of the chip, and a kernel's share of its roofline.

The least time a call can take is the larger of its operations over the
peak rate of the unit that runs them and its bytes over the HBM bandwidth
(``work/<kernel>.py`` counts both from the call's shapes). The share is
that least time, times the calls, over the kernel's summed device time.
A share above 100% means the work is counted too high or the time misses
part of the call: it is an error, never clipped.
"""
from __future__ import annotations

from .spec import BENCH, load_json, load_module

OPS_PEAK = {"bf16": "bf16_flops_per_s", "int8": "int8_ops_per_s"}


class UnknownDevice(KeyError):
    pass


class RooflineError(ValueError):
    pass


def peaks(device_kind: str, bench=BENCH) -> dict:
    table = load_json(bench / "peaks.json")["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def least_seconds(work: dict, peak: dict) -> float:
    """Least time of one call: ``work`` holds ``bytes`` and, per unit kind
    of ``OPS_PEAK``, the operations that run there (``ops``: {kind: n})."""
    t = work["bytes"] / peak["hbm_bytes_per_s"]
    for kind, n in work.get("ops", {}).items():
        t = max(t, n / peak[OPS_PEAK[kind]])
    return t


def share(kernel: str, shape: dict, calls: float, seconds: float,
          peak: dict, bench=BENCH) -> float:
    """Roofline share of ``calls`` calls at ``shape`` taking ``seconds``, in %."""
    work = load_module("work", kernel, bench).work(**shape)
    pct = 100.0 * calls * least_seconds(work, peak) / seconds
    if pct > 100.0:
        raise RooflineError(
            f"{kernel}: {pct:.1f}% of its roofline ({calls} calls of {work} in "
            f"{seconds} s): the work is counted too high or the time too low")
    return pct

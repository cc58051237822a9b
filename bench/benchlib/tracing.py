"""Profiler trace of the measured window, and its reduction to numbers.

``extract`` turns the profiler's ``.xplane.pb`` into two plain lists: the
device's operations (``[label, start_ns, dur_ns, kernel]`` per op on the
"XLA Ops" line, ``kernel`` naming the Pallas kernel it is, if any) and the host's spans
(``[name, start_ns, dur_ns]`` on the thread that drove the window).
``reduce`` works on those lists alone, so a small recorded trace checks it
(``bench/tests``).

From the device ops inside the window (the host span ``bench.window``):
busy time is the union of the op intervals, averaged over the chips; a
kernel's time is the sum of its ops' durations, and its calls their number.
Each idle gap between device ops is attributed to what the host was doing
at its midpoint: the innermost host span there, under the ``bench.*`` span
that holds it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10  # entries in each list of the breakdown


def start(trace_dir: Path) -> None:
    import jax

    if trace_dir.exists():
        shutil.rmtree(trace_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python calls stay off: they slow the host
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


HLO_OP = re.compile(r"^%?([^\s=]+)\s*=\s*(\S+)")


def kernel_of(texts, kernels) -> str | None:
    """The kernel (by name) an op is, judged from its name and stats: the
    name as a whole path component, or followed by ``.<n>``."""
    for kern in kernels:
        pat = re.compile(rf"(^|/){re.escape(kern)}(\.\d+)?(/|$)")
        if any(pat.search(t) for t in texts):
            return kern
    return None


def device_op(text: str, stat_texts, kernels) -> tuple[str, str | None]:
    """``(label, kernel)`` of one device op. A TPU trace names an op by its
    HLO text (``%fusion.13 = u32[262144,24]{...} fusion(...)``): the label
    is the op's name and result type, the kernel is judged from the name."""
    m = HLO_OP.match(text)
    if not m:
        return text[:80], kernel_of([text, *stat_texts], kernels)
    name, result = m.groups()
    kern = kernel_of([name, *stat_texts], kernels)
    return kern or f"{name} {re.sub(r'{[^}]*}', '', result)}"[:80], kern


def extract(xplane_path: str, kernels) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    device, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    label, kern = device_op(
                        e.name, [v for _, v in e.stats if isinstance(v, str)],
                        kernels)
                    ops.append([label, float(e.start_ns), float(e.duration_ns),
                                kern])
            device[m.group(1)] = ops
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events]
                if any(n == WINDOW_SPAN for n, _, _ in evs):
                    host = evs
    return {"device": device, "host": host}


def find_xplane(trace_dir: Path) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over chips
    kernel_s: dict  # kernel -> summed device seconds (mean over chips)
    kernel_calls: dict  # kernel -> calls (mean over chips)
    device_ops: list  # [[name, seconds]] largest first, at most TOP
    idle_gaps: list  # [[host activity, seconds]] largest first, at most TOP

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _activities(host, times) -> list:
    """For each of the sorted ``times``: ``<bench span>/<innermost span>``
    holding it on the host. Spans on one thread nest, so the spans open at
    ``t`` form a stack, innermost on top."""
    spans = sorted(host, key=lambda h: (h[1], -h[2]))
    out, stack, j = [], [], 0
    for t in times:
        while j < len(spans) and spans[j][1] <= t:
            while stack and stack[-1][1] + stack[-1][2] <= spans[j][1]:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][1] + stack[-1][2] <= t:
            stack.pop()
        holding = [h[0] for h in stack if h[0] != WINDOW_SPAN]
        if not holding:
            out.append("no host span")
            continue
        outer = next((n for n in holding if n.startswith("bench.")),
                     "outside bench spans")
        out.append(outer if holding[-1] == outer else f"{outer}/{holding[-1]}")
    return out


def reduce(extracted: dict) -> TraceSummary:
    host = extracted["host"]
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    devices = extracted["device"]
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line in the trace")
    busy, kern_s, kern_n, op_s, gaps = 0.0, {}, {}, {}, {}
    for ops in devices.values():
        inside = [o for o in ops if o[1] < w1 and o[1] + o[2] > w0]
        ivs = _union((max(o[1], w0), min(o[1] + o[2], w1)) for o in inside)
        busy += sum(e - s for s, e in ivs)
        for label, _, dur, kern in inside:
            key = kern or label
            op_s[key] = op_s.get(key, 0.0) + dur
            if kern:
                kern_s[kern] = kern_s.get(kern, 0.0) + dur
                kern_n[kern] = kern_n.get(kern, 0) + 1
        edges = [w0] + [x for iv in ivs for x in iv] + [w1]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for (s, e), what in zip(idle, _activities(host, [(s + e) / 2 for s, e in idle])):
            gaps[what] = gaps.get(what, 0.0) + (e - s)
    nd = len(devices)

    def top(d):
        return [[k, v / nd * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / nd * 1e-9,
        kernel_s={k: v / nd * 1e-9 for k, v in kern_s.items()},
        kernel_calls={k: v / nd for k, v in kern_n.items()},
        device_ops=top(op_s), idle_gaps=top(gaps))

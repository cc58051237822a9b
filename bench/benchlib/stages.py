"""Device time per search stage, and idle time per engine span, from the
traced window.

The program names its search stages with ``jax.named_scope``
(``lider.route``, ``lider.candidates``, ``lider.sketch``,
``lider.code_pass``, ``lider.rescore``), so each device op's name or stats
hold its stage as a path component, as they hold a Pallas kernel's name;
``tracing.kernel_of`` finds both. An op with no stage is ``unattributed``
and kept by its label. Its engine wraps each batch boundary in a
``TraceAnnotation`` (``engine.take_batch``, ``engine.h2d``,
``engine.dispatch``, ``engine.wait``, ``engine.host_fetch``,
``engine.d2h``, ``engine.record``); each idle gap between device ops goes
to the innermost ``engine.*`` span at its midpoint, under the ``bench.*``
span that holds it.

``extract`` reads the ``.xplane.pb`` into the plain lists of
``tracing.extract`` plus ``stage``, each device op's stage (or None) in the
order of its ops, judged from the op's event metadata (its ``tf_op``
op-name path); ``reduce`` works on those alone, so a small recorded
trace checks it (``bench/tests``). A trace without ``stage``, or of a
program without the scopes, reduces to every op unattributed, and the stage
metrics read nothing there.
"""
from __future__ import annotations

import dataclasses

from . import spec, tracing
from .runner import log

STAGES = ("lider.route", "lider.candidates", "lider.sketch",
          "lider.code_pass", "lider.rescore")
UNATTRIBUTED = "unattributed"


def trace_dir(cell: str):
    """Where ``runner.run`` writes a traced window's profile."""
    return spec.ROOT / "build" / "bench_trace" / cell


def extract(xplane_path: str, kernels) -> dict:
    """What ``tracing.extract`` gives (``device``: ``[label, start, dur,
    kernel]`` per op; ``host``: the window thread's spans), plus ``stage``:
    ``{chip: [stage or None per op]}`` in the same order."""
    extracted = tracing.extract(xplane_path, kernels)
    stage = _op_stages(xplane_path)
    for chip, ops in extracted["device"].items():
        if len(stage.get(chip, ())) != len(ops):
            raise ValueError(f"chip {chip}: {len(ops)} ops but "
                             f"{len(stage.get(chip, ()))} stages")
    extracted["stage"] = stage
    return extracted


def _op_stages(xplane_path: str) -> dict:
    """``{chip: [stage or None]}`` for the ops of each device's "XLA Ops"
    line, in trace order. A device op's op-name path (``tf_op``) sits in
    its event metadata, which ``jax.profiler.ProfileData`` does not show,
    so this reads the XSpace proto itself."""
    space = _xspace_class()()
    with open(xplane_path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        stage_of = {}
        for e in plane.event_metadata:
            md = e.value
            texts = [md.name, md.display_name]
            for st in md.stats:
                if st.str_value:
                    texts.append(st.str_value)
                elif st.ref_value:
                    texts.append(stat_names.get(st.ref_value, ""))
            stage_of[e.key] = tracing.kernel_of(texts, STAGES)
        out[m.group(1)] = [stage_of.get(ev.metadata_id)
                           for line in plane.lines
                           if line.name == tracing.OPS_LINE
                           for ev in line.events]
    return out


def _xspace_class():
    """The parts of the profiler's ``XSpace`` proto (tsl ``xplane.proto``)
    this module reads, as a message class; unknown fields are skipped."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    T = descriptor_pb2.FieldDescriptorProto
    schema = {
        "XSpace": [("planes", 1, "XPlane")],
        "XPlane": [("name", 2, T.TYPE_STRING), ("lines", 3, "XLine"),
                   ("event_metadata", 4, "EventMetadataEntry"),
                   ("stat_metadata", 5, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, T.TYPE_INT64),
                               ("value", 2, "XEventMetadata", 1)],
        "StatMetadataEntry": [("key", 1, T.TYPE_INT64),
                              ("value", 2, "XStatMetadata", 1)],
        "XLine": [("name", 2, T.TYPE_STRING), ("events", 4, "XEvent")],
        "XEvent": [("metadata_id", 1, T.TYPE_INT64)],
        "XEventMetadata": [("name", 2, T.TYPE_STRING),
                           ("display_name", 4, T.TYPE_STRING),
                           ("stats", 5, "XStat")],
        "XStatMetadata": [("name", 2, T.TYPE_STRING)],
        "XStat": [("str_value", 5, T.TYPE_STRING),
                  ("ref_value", 7, T.TYPE_UINT64)],
    }
    for msg, fields in schema.items():
        d = f.message_type.add(name=msg)
        for name, number, kind, *one in fields:
            if isinstance(kind, str):  # a message: repeated unless marked
                d.field.add(name=name, number=number, type=T.TYPE_MESSAGE,
                            type_name=f".bench_xplane.{kind}",
                            label=T.LABEL_OPTIONAL if one
                            else T.LABEL_REPEATED)
            else:
                d.field.add(name=name, number=number, type=kind,
                            label=T.LABEL_OPTIONAL)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


@dataclasses.dataclass
class StageSummary:
    window_s: float
    op_s: float  # summed device op time in the window (mean over chips)
    stage_s: dict  # stage -> summed device seconds, UNATTRIBUTED included
    unattributed: list  # [[label, seconds]] largest first, at most TOP
    idle: list  # [[<bench span>/<engine span>, seconds]] largest first

    @property
    def coverage(self) -> float:
        """Share of the summed device op time that carries a stage."""
        if not self.op_s:
            return 0.0
        return 1.0 - self.stage_s.get(UNATTRIBUTED, 0.0) / self.op_s


def reduce(extracted: dict) -> StageSummary:
    host = extracted["host"]
    win = [h for h in host if h[0] == tracing.WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {tracing.WINDOW_SPAN} span in the trace")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    devices = extracted["device"]
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line in the trace")
    # Only the bench's and the engine's spans name an activity, so the
    # innermost one open at a gap is the engine span, not a JAX internal.
    spans = [h for h in host if h[0].startswith(("bench.", "engine."))]
    total, stage_s, unattr, idle = 0.0, {}, {}, {}
    for chip, ops in devices.items():
        staged = extracted.get("stage", {}).get(chip) or [None] * len(ops)
        inside = [(o, st) for o, st in zip(ops, staged)
                  if o[1] < w1 and o[1] + o[2] > w0]
        for (label, _, dur, _), stage in inside:
            total += dur
            key = stage or UNATTRIBUTED
            stage_s[key] = stage_s.get(key, 0.0) + dur
            if not stage:
                unattr[label] = unattr.get(label, 0.0) + dur
        ivs = tracing._union((max(o[1], w0), min(o[1] + o[2], w1))
                             for o, _ in inside)
        edges = [w0] + [x for iv in ivs for x in iv] + [w1]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        mids = [(s + e) / 2 for s, e in gaps]
        for (s, e), what in zip(gaps, tracing._activities(spans, mids)):
            idle[what] = idle.get(what, 0.0) + (e - s)
    nd = len(devices)

    def ranked(d, top=None):
        items = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / nd * 1e-9] for k, v in items]

    return StageSummary(
        window_s=(w1 - w0) * 1e-9, op_s=total / nd * 1e-9,
        stage_s={k: v / nd * 1e-9 for k, v in stage_s.items()},
        unattributed=ranked(unattr, tracing.TOP), idle=ranked(idle))


def of_run(run) -> StageSummary | None:
    """The stage summary of a traced run's window, reduced and logged once
    per run (kept on ``run``); None for an untraced run."""
    if run.trace is None:
        return None
    if getattr(run, "stages", None) is None:
        work = run.cell.bench / "work"
        kernels = sorted(p.stem for p in work.glob("*.py"))
        s = reduce(extract(tracing.find_xplane(trace_dir(run.cell.name)),
                           kernels))
        run.stages = s
        log("device seconds by stage: " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(s.stage_s.items(),
                                              key=lambda kv: -kv[1]))
            + f"; stages cover {100 * s.coverage:.2f}% of {s.op_s:.4f} s")
        log("unattributed ops: " + ", ".join(
            f"{k} {v:.4f}" for k, v in s.unattributed))
        log("idle seconds by engine span: " + ", ".join(
            f"{k} {v:.4f}" for k, v in s.idle))
    return run.stages


def per_batch_ms(run, stage: str) -> float | None:
    """Device ms of ``stage`` per batch recorded in the window; None where
    the trace holds no op of that stage."""
    s = of_run(run)
    n = run.window.stats.get("n_batches", 0)
    if s is None or not s.stage_s.get(stage) or not n:
        return None
    return s.stage_s[stage] / n * 1e3

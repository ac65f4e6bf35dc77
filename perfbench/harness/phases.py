"""Name a profiler trace's time by the program's own spans and scopes.

The program marks each host phase of a refresh with a span
(``repro.core.spans``: a ``jax.profiler.TraceAnnotation`` named
``repro.<layer>.<phase>``, written ``name#epoch=N#`` or with an ``epoch``
stat) and each stage of the merge with ``jax.named_scope``
(``shuffle_reduce/<stage>``), which XLA keeps in each operation's
``op_name`` and the profiler in the ``tf_op`` stat of the operation's
event metadata.

- ``idle_by_span``: the device's idle seconds in the window, each idle
  interval split at span boundaries and put down to the innermost
  ``repro.*`` span that covers it, else to ``untraced``;
- ``device_by_scope``: device seconds of the merge program's operations
  (``_merge_reduce``) per ``shuffle_reduce/*`` scope, else ``unscoped``.

``jax.profiler.ProfileData`` reads events but not their metadata's stats,
so the scopes come from the raw ``XSpace`` protobuf.  Its Python module
ships with tensorflow; it is loaded from its file, without importing
tensorflow (some ten seconds).  Where it is missing, ``device_by_scope``
is None.
"""
from __future__ import annotations

import importlib.util
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from perfbench.harness import trace

SPAN_PREFIX = "repro."
UNTRACED = "untraced"
UNSCOPED = "unscoped"
PROGRAM = "_merge_reduce"
SCOPE = re.compile(r"(shuffle_reduce/[A-Za-z_]+)")
XPLANE_PB2 = ("tsl", "profiler", "protobuf", "xplane_pb2.py")

Interval = Tuple[str, float, float]          # (name, start s, end s)


@dataclass
class Phases:
    idle_by_span: Dict[str, float]           # span name -> idle seconds
    device_by_scope: Optional[Dict[str, float]]   # scope -> device seconds


def span_name(event: str) -> str:
    """A host event's name without the ``#key=value#`` its stats may be
    encoded in."""
    return event.split("#", 1)[0]


def host_spans(path: Path) -> List[Interval]:
    """The ``repro.*`` host spans of the trace, in seconds on its clock."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = span_name(ev.name)
                if name.startswith(SPAN_PREFIX):
                    out.append((name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


def innermost(spans: Sequence[Interval]
              ) -> Tuple[np.ndarray, List[str]]:
    """Every span boundary, sorted, and between each two the name of the
    innermost span covering that piece (the latest to start; spans of one
    thread nest), or ``untraced``."""
    edges = np.unique([t for _, s, e in spans for t in (s, e)])
    labels = []
    for a, b in zip(edges[:-1], edges[1:]):
        mid = 0.5 * (a + b)
        inner = max(((s, -e, k) for k, s, e in spans if s <= mid < e),
                    default=None)
        labels.append(inner[2] if inner else UNTRACED)
    return edges, labels


def idle_by_span(gaps: Sequence[Tuple[float, float]],
                 spans: Sequence[Interval]) -> Dict[str, float]:
    """Seconds of the idle intervals ``gaps``, split at span boundaries,
    per innermost covering span."""
    edges, labels = innermost(spans)
    out: Dict[str, float] = {}
    for a, b in gaps:
        cuts = np.concatenate([[a], edges[(edges > a) & (edges < b)], [b]])
        for x, y in zip(cuts[:-1], cuts[1:]):
            i = int(np.searchsorted(edges, 0.5 * (x + y), side="right")) - 1
            name = labels[i] if 0 <= i < len(labels) else UNTRACED
            out[name] = out.get(name, 0.0) + float(y - x)
    return out


def load_xplane_pb2():
    """tensorflow's ``xplane_pb2`` module, loaded from its file, or None."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        return None
    for root in spec.submodule_search_locations:
        path = Path(root).joinpath(*XPLANE_PB2)
        if path.is_file():
            mod_spec = importlib.util.spec_from_file_location(
                "perfbench_xplane_pb2", path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            return mod
    return None


def device_scoped_ops(path: Path, pb2) -> List[dict]:
    """Per device: ``ops``, [(tf_op, start, end)], and ``modules``,
    [(program, start, end)], in seconds on the trace's clock."""
    space = pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out = []
    for plane in space.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        stat = {k: m.name for k, m in plane.stat_metadata.items()}
        tf_op = {}
        for k, meta in plane.event_metadata.items():
            tf_op[k] = next((s.str_value for s in meta.stats
                             if stat.get(s.metadata_id) == "tf_op"), "")
        lines = {}
        for line in plane.lines:
            base = line.timestamp_ns * 1e-9
            lines[line.name] = [
                (ev.metadata_id, base + ev.offset_ps * 1e-12,
                 base + (ev.offset_ps + ev.duration_ps) * 1e-12)
                for ev in line.events]
        out.append({
            "ops": [(tf_op.get(k, ""), s, e)
                    for k, s, e in lines.get(trace.OPS_LINE, [])],
            "modules": [(plane.event_metadata[k].name, s, e)
                        for k, s, e in lines.get(trace.MODULES_LINE, [])]})
    return out


def device_by_scope(devices: Sequence[dict], lo: float, hi: float,
                    program: str = PROGRAM) -> Dict[str, float]:
    """Device seconds, inside [lo, hi], of the operations that run inside
    a run of ``program``, per ``shuffle_reduce/*`` scope of their
    ``tf_op``, else ``unscoped``; mean over the devices.

    The ops line nests a loop's body operations inside the loop's own
    event, so each event counts its self time: its length less that of
    the events it holds.  An event with no scope (a loop has no
    ``tf_op``) takes the scope of the event that holds it, else that of
    the events it holds.
    """
    out: Dict[str, float] = {}
    for dev in devices:
        runs = trace.merge([(s, e) for name, s, e in dev["modules"]
                            if program in name])
        starts = np.array([s for s, _ in runs])
        events = []
        for tf_op, s, e in dev["ops"]:
            s, e = max(s, lo), min(e, hi)
            i = int(np.searchsorted(starts, 0.5 * (s + e), side="right")) - 1
            if e > s and i >= 0 and 0.5 * (s + e) < runs[i][1]:
                m = SCOPE.search(tf_op)
                events.append((s, e, m.group(1) if m else None))
        events.sort(key=lambda ev: (ev[0], -ev[1]))
        parent, held = [-1] * len(events), []
        for i, (s, e, _) in enumerate(events):
            while held and events[held[-1]][1] <= s:
                held.pop()
            parent[i] = held[-1] if held else -1
            held.append(i)
        own = [e - s for s, e, _ in events]
        scope = [k for _, _, k in events]
        for i, p in enumerate(parent):          # holders come first
            if p >= 0:
                own[p] -= events[i][1] - events[i][0]
                scope[i] = scope[i] or scope[p]
        for i in reversed(range(len(events))):
            p = parent[i]
            if p >= 0 and scope[p] is None:
                scope[p] = scope[i]
        for t, k in zip(own, scope):
            out[k or UNSCOPED] = out.get(k or UNSCOPED, 0.0) + t
    n = max(len(devices), 1)
    return {k: v / n for k, v in out.items()}


def summarize(path: Path, closed: float,
              program: str = PROGRAM) -> Phases:
    """The phases of the window that opens at the ``perfbench.t0``
    annotation of the trace file ``path`` and closes ``closed`` seconds
    later."""
    anchor, devices = trace.load_xplane(path)
    if anchor is None:
        raise ValueError(f"trace has no {trace.ANCHOR!r} annotation")
    lo, hi = anchor, anchor + closed
    spans = [(k, max(s, lo), min(e, hi)) for k, s, e in host_spans(path)
             if e > lo and s < hi]
    gaps = []
    for lines in devices:
        busy = [(s, e) for _, s, e in lines.get(trace.OPS_LINE, [])]
        gaps += trace.idle_gaps(np.array(busy, float), lo, hi)
    n = max(len(devices), 1)
    idle = {k: v / n for k, v in idle_by_span(gaps, spans).items()}
    pb2 = load_xplane_pb2()
    scoped = (None if pb2 is None else
              device_by_scope(device_scoped_ops(path, pb2), lo, hi, program))
    return Phases(idle, scoped)

"""Reduce a profiler trace (``.xplane.pb``) to the device's busy time,
per-program device time, the costliest operations and the longest idle
gaps, each gap named by what the host was doing in it.

Device planes are ``/device:TPU:<n>``.  On each, the ``XLA Ops`` line
holds one event per operation run, and ``XLA Modules`` one per program
(jitted function) run.  Busy time is the union of the operation events
inside the window.  The host's ``perfbench.t0`` annotation ties the
benchmark's host clock to the trace's.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANCHOR = "perfbench.t0"
# host spans, most specific first: a gap inside a poll is named "poll"
SPAN_ORDER = ("poll", "fetch", "refresh", "step")
TOP = 10
NAME_CHARS = 160                       # an operation's HLO text, cut


@dataclass
class TraceSummary:
    busy_s: float                       # mean over the devices
    window_s: float
    devices: int
    modules: Dict[str, float]           # program name -> device seconds
    ops: List[Tuple[str, float]]        # costliest operations (their HLO
                                        # text, cut), seconds
    gaps: List[Tuple[str, float]]       # longest idle gaps, seconds


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union_length(iv: np.ndarray) -> float:
    """Total length covered by intervals ``iv`` ([n, 2], any order)."""
    return float(sum(e - s for s, e in merge(iv)))


def merge(iv: np.ndarray) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(map(tuple, np.asarray(iv, float).reshape(-1, 2))):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.asarray(iv, float).reshape(-1, 2)
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return iv[iv[:, 1] > iv[:, 0]]


def idle_gaps(busy: np.ndarray, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    gaps, at = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def name_gap(gap: Tuple[float, float],
             spans: Sequence[Tuple[str, float, float]]) -> str:
    """The most specific host span that covers the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    kinds = {k for k, s, e in spans if s <= mid < e}
    for k in SPAN_ORDER:
        if k in kinds:
            return k
    return "wait"


def reduce_events(devices: List[Dict[str, List[Tuple[str, float, float]]]],
                  lo: float, hi: float,
                  spans: Sequence[Tuple[str, float, float]]
                  ) -> TraceSummary:
    """``devices``: per device, line name -> [(event, start, end)] in
    seconds on the same clock as ``lo``, ``hi`` and ``spans``."""
    busy_total, modules, ops = 0.0, {}, {}
    gaps: List[Tuple[str, float]] = []
    for lines in devices:
        op_events = lines.get(OPS_LINE) or [
            ev for name, evs in lines.items() if name != "Steps"
            for ev in evs]
        iv = np.array([(s, e) for _, s, e in op_events], float)
        busy_total += union_length(clip(iv, lo, hi))
        for name, s, e in op_events:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d
        for name, s, e in lines.get(MODULES_LINE, []):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                modules[name] = modules.get(name, 0.0) + d
        gaps += idle_gaps(iv, lo, hi)
    n = max(len(devices), 1)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return TraceSummary(
        busy_s=busy_total / n, window_s=hi - lo, devices=len(devices),
        modules=modules,
        ops=[(k[:NAME_CHARS], v) for k, v in
             sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        gaps=[(name_gap(g, spans), float(g[1] - g[0])) for g in longest])


def load_xplane(path: Path):
    """(anchor start in seconds or None, per-device line events) with
    every time in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    anchor: Optional[float] = None
    devices = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append({
                line.name: [(ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events]
                for line in plane.lines})
        elif plane.name.startswith("/host:") and anchor is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = ev.start_ns * 1e-9
                        break
    return anchor, devices


def summarize(log_dir: Path, t0: float, closed: float,
              spans: Sequence[Tuple[str, float, float]]) -> TraceSummary:
    """Reduce the trace under ``log_dir`` to the window that started at
    host time ``t0`` and closed ``closed`` seconds later."""
    anchor, devices = load_xplane(find_xplane(log_dir))
    if anchor is None:
        raise ValueError(f"trace has no {ANCHOR!r} annotation")
    shift = anchor - t0                 # host clock -> trace clock
    moved = [(k, s + shift, e + shift) for k, s, e in spans]
    return reduce_events(devices, anchor, anchor + closed, moved)

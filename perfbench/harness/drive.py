"""Warm-up and the measured window, driven from one thread.

The window offers the traffic's events on their schedule, whatever the
session does, and calls ``step()`` in a loop.  After each step that ran
a refresh it fetches ``result``; every event whose sequence number the
session's applied watermark (``metrics.last_epoch``) has reached is
reflected at the end of that fetch.  Its freshness is that moment less
its due time.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import List, Tuple

import jax
import numpy as np

from perfbench.harness.source import ScheduledSource

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# how long past the window's close a due event may still be reflected
GRACE_S = 60.0


class CompileCounter:
    """Counts XLA backend compiles while ``active`` (a persistent-cache
    hit does not compile and is not counted)."""

    def __init__(self):
        self.active = False
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT and self.active:
            with self._lock:
                self.count += 1


@dataclass
class Refresh:
    """One step that ran a refresh, timed on the host clock from the
    step's start to the end of the result fetch after it."""

    start: float
    end: float
    rows: int                  # delta rows the batch took in
    iters: int                 # engine iterations (RunReport.iters)
    store_bytes_read: int      # RunReport.io.bytes_read
    store_rows_appended: int   # growth of the MRBG file, in records
    affected_keys: int         # keys re-reduced (RunReport.affected_keys)
    record_bytes: int          # bytes of one MRBG record
    compiles_before: int       # window compiles counted up to its end


@dataclass
class Window:
    t0: float                  # host clock at the window's start
    seconds: float
    due: np.ndarray            # per event: due, seconds from t0
    reflected: np.ndarray      # per event: reflected, seconds from t0 (nan)
    taken: np.ndarray          # per event: start of the step that
                               # reflected it, seconds from t0 (nan)
    refreshes: List[Refresh]   # refreshes that started in the window
    compiles: int              # backend compiles inside the window
    closed: float              # seconds from t0 when the last step
                               # started in the window ended
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def _refresh(ss, start: float, end: float, prev_store: int,
             compiled: int) -> Refresh:
    rep = ss.session.history[-1]
    store = ss.session.store
    rb = store.record_bytes if store is not None else 1
    grown = rep.store_bytes - prev_store
    return Refresh(
        start=start, end=end,
        rows=int((rep.coalesce or {}).get("n_in", 0)),
        iters=int(rep.iters),
        store_bytes_read=int(rep.io.bytes_read) if rep.io is not None else 0,
        store_rows_appended=grown // rb if grown > 0 else 0,
        affected_keys=max(int(rep.affected_keys), 0),
        record_bytes=rb, compiles_before=compiled)


def run_window(ss, source: ScheduledSource, offsets: np.ndarray,
               first: int, lead: float, seconds: float,
               compiles: CompileCounter, *, base: int = 0,
               trace: bool = False, hold_at_close: bool = False,
               on_open=None, on_close=None) -> Window:
    """Offer event ``base + i`` at ``start + offsets[i]`` and step the
    session, from ``start`` (now) on.  Events before ``first`` are the
    lead-in: the first ``lead`` seconds of the same traffic, which bring
    the session to its steady state (its batch sizes, its queue) before
    the window.  The window opens at the first step boundary from
    ``start + lead`` on and lasts ``seconds``; the events from ``first``
    on are the window's.  After it the session keeps stepping until every
    window event is reflected, or ``GRACE_S`` has passed.  With
    ``hold_at_close`` (a backlog, all due at once) the source hands out
    nothing more once the window has closed: the window's events are
    those the session took before then, and the rest are not attempted.

    ``on_open`` runs as the window opens (the traced run starts the
    profiler there); ``on_close`` once the last step started in the
    window has ended (and stops it).  Times in the Window are from the
    window's opening.
    """
    n = len(offsets) - first
    first += base                      # sequence numbers from here on
    last_seq = base + len(offsets) - 1
    reflected = np.full(n, np.nan)     # host clock, absolute
    taken = np.full(n, np.nan)
    refreshes: List[Refresh] = []
    spans: List[Tuple[str, float, float]] = []
    annotate = (jax.profiler.TraceAnnotation if trace
                else lambda name: contextlib.nullcontext())
    start = time.perf_counter()
    due = start + np.asarray(offsets, float)
    source.release(base, due)
    t0 = end = np.inf
    closed = None
    prev_store = ss.session.history[-1].store_bytes
    done = base                        # next event not yet reflected
    while done <= last_seq:
        now = time.perf_counter()
        if t0 == np.inf and now >= start + lead:
            if on_open is not None:
                on_open()
            source.spans = spans if trace else None
            # a point on the trace's clock at the opening: the trace
            # reduction aligns the host spans with the device through it
            with annotate("perfbench.t0"):
                t0 = now = time.perf_counter()
            end = t0 + seconds
            compiles.active = True
        if closed is None and now >= end:
            compiles.active = False
            closed = now - t0
            source.spans = None
            if on_close is not None:
                on_close()
            if hold_at_close:
                last_seq = min(last_seq, source.hold())
                if done > last_seq:
                    break
        if now >= end + GRACE_S:
            break
        with annotate("perfbench.step"):
            ran = ss.step()
        t1 = time.perf_counter()
        inside = t0 <= now and closed is None
        if not ran:
            if trace and inside:
                spans.append(("step", now, t1))
            wait = min(source.next_due(), t1 + 0.002) - t1
            if wait > 0:
                time.sleep(wait)
            continue
        with annotate("perfbench.fetch"):
            ss.result
        tf = time.perf_counter()
        if inside:
            refreshes.append(_refresh(ss, now, tf, prev_store,
                                      compiles.count))
            if trace:
                spans += [("refresh", now, t1), ("fetch", t1, tf)]
        prev_store = ss.session.history[-1].store_bytes
        hi = min(ss.metrics.last_epoch, last_seq)
        lo = max(done, first)
        if hi >= lo:
            reflected[lo - first:hi - first + 1] = tf
            taken[lo - first:hi - first + 1] = now
        done = max(done, hi + 1)
    if closed is None:                 # every event reflected in the window
        while time.perf_counter() < end:
            time.sleep(min(0.01, max(end - time.perf_counter(), 0)))
        compiles.active = False
        closed = time.perf_counter() - t0
        source.spans = None
        if on_close is not None:
            on_close()
    n = last_seq - first + 1
    return Window(t0=t0, seconds=seconds,
                  due=due[first - base:][:n] - t0,
                  reflected=reflected[:n] - t0, taken=taken[:n] - t0,
                  refreshes=refreshes, compiles=compiles.count,
                  closed=closed, spans=spans)

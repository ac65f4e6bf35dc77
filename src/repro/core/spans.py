"""Host spans and counters of one refresh.

``span(name)`` times a phase of the host's work and ``count(name, n)``
adds to a counter; ``take()`` hands both over and clears them.
``Session`` takes them once per epoch into the epoch's ``RunReport``
(``spans``, ``counters``), so they are kept no longer than the report
history.  ``to_device`` and ``to_host`` move an array and count its bytes
as ``h2d_bytes`` or ``d2h_bytes``.

Each span also opens a ``jax.profiler.TraceAnnotation`` that carries its
epoch, so a profiler trace shows it on the host plane, on the clock of
the device's operations.  A span adds no wait for the device: it opens
and closes where the host code already is, and one that holds a
device-to-host pull includes the device time the pull waits for.

The record is per thread: the spans of a refresh are those of the thread
that runs it.  Spans nest; each names the span that encloses it, and
inherits its epoch unless given one.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

H2D = "h2d_bytes"
D2H = "d2h_bytes"


@dataclass
class Span:
    name: str
    parent: Optional[str]          # the enclosing span's name
    epoch: Optional[int]           # shared by the spans of one micro-batch
    start: float                   # time.perf_counter()
    end: Optional[float] = None    # None while the span is open


class _Record(threading.local):
    def __init__(self):
        self.spans: List[Span] = []
        self.counters: Dict[str, int] = {}
        self.open: List[Span] = []


_record = _Record()


class span:
    """Context manager: time the enclosed host work as ``name``."""

    __slots__ = ("_span", "_note")

    def __init__(self, name: str, epoch: Optional[int] = None):
        outer = _record.open[-1] if _record.open else None
        if epoch is None and outer is not None:
            epoch = outer.epoch
        self._span = Span(name, outer.name if outer else None, epoch, 0.0)
        self._note = (jax.profiler.TraceAnnotation(name) if epoch is None
                      else jax.profiler.TraceAnnotation(name, epoch=epoch))

    def __enter__(self) -> Span:
        self._note.__enter__()
        s = self._span
        _record.spans.append(s)
        _record.open.append(s)
        s.start = time.perf_counter()
        return s

    def __exit__(self, *exc) -> bool:
        self._span.end = time.perf_counter()
        _record.open.pop()
        self._note.__exit__(*exc)
        return False


def count(name: str, n: int) -> None:
    c = _record.counters
    c[name] = c.get(name, 0) + int(n)


def take() -> Tuple[List[Span], Dict[str, int]]:
    """The spans (in the order they opened) and counters recorded since the
    last call, which the record forgets.  A span still open is returned
    too; its ``end`` is set when it closes."""
    out = _record.spans, _record.counters
    _record.spans, _record.counters = [], {}
    return out


def to_device(a, dtype=None) -> jax.Array:
    """``jnp.asarray(a, dtype)``; a host array's bytes count as
    ``h2d_bytes``."""
    out = jnp.asarray(a, dtype)
    if not isinstance(a, jax.Array):
        count(H2D, out.nbytes)
    return out


def to_host(a) -> np.ndarray:
    """``np.asarray(a)``; a device array's bytes count as ``d2h_bytes``."""
    out = np.asarray(a)
    if isinstance(a, jax.Array):
        count(D2H, out.nbytes)
    return out

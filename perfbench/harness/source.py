"""The benchmark's delta source: records released on a schedule.

The session polls it from inside ``step()``.  A record is handed out
once it is due; the record's ``epoch`` is its sequence number, so the
session's applied watermark (``metrics.last_epoch``) says which records
a result reflects.
"""
from __future__ import annotations

import time
from typing import List, Sequence

import numpy as np

from repro.stream.source import DeltaRecord, DeltaSource


class ScheduledSource(DeltaSource):
    def __init__(self, records: Sequence[DeltaRecord]):
        self.records = list(records)
        for i, r in enumerate(self.records):
            if r.epoch != i:
                raise ValueError("record epochs must be 0, 1, 2, ...")
        self.due = np.full(len(self.records), np.inf)
        self.cursor = 0
        # when a list: each poll appends ("poll", start, end) on the host
        # clock (the traced run names the device's idle gaps with them)
        self.spans = None

    def release(self, start: int, dues: np.ndarray) -> None:
        """Make records ``start, start+1, ...`` due at ``dues`` (host
        perf_counter seconds, ascending)."""
        self.due[start:start + len(dues)] = dues

    def hold(self) -> int:
        """Hand out no record not handed out yet; returns the sequence
        number of the last one handed out."""
        self.due[self.cursor:] = np.inf
        return self.cursor - 1

    def next_due(self) -> float:
        return (float(self.due[self.cursor])
                if self.cursor < len(self.records) else np.inf)

    def poll(self, max_rows: int) -> List[DeltaRecord]:
        now = time.perf_counter()
        out: List[DeltaRecord] = []
        rows = 0
        while (self.cursor < len(self.records) and rows < max_rows
               and self.due[self.cursor] <= now):
            rec = self.records[self.cursor]
            out.append(rec)
            rows += rec.n_rows
            self.cursor += 1
        if self.spans is not None:
            self.spans.append(("poll", now, time.perf_counter()))
        return out

    @property
    def exhausted(self) -> bool:
        return self.cursor >= len(self.records)

    @property
    def watermark(self) -> int:
        return self.cursor - 1

"""The window's arithmetic: the delta rate counts the refresh running at
the close by its share inside the window, and a held source hands out
nothing more."""
import numpy as np

from perfbench.harness import drive
from perfbench.harness.cell import end_to_end
from perfbench.harness.source import ScheduledSource
from repro.stream.source import DeltaRecord


def window(spans, seconds=10.0, t0=100.0):
    refreshes = [drive.Refresh(start=t0 + a, end=t0 + b, rows=4096, iters=1,
                               store_bytes_read=0, store_rows_appended=0,
                               affected_keys=0, record_bytes=1,
                               compiles_before=0) for a, b in spans]
    return drive.Window(t0=t0, seconds=seconds, due=np.zeros(3),
                        reflected=np.ones(3), taken=np.zeros(3),
                        refreshes=refreshes, compiles=0, closed=seconds)


def test_rate_counts_the_straddling_refresh_in_part():
    whole = end_to_end(window([(0, 4), (4, 8)]), 1.0)
    part = end_to_end(window([(0, 4), (4, 8), (8, 12)]), 1.0)
    assert whole["delta_rows_per_s"] == 2 * 4096 / 10
    assert np.isclose(part["delta_rows_per_s"], 2.5 * 4096 / 10)


def test_rate_moves_with_the_refresh_time():
    slow = end_to_end(window([(0, 3.3), (3.3, 6.6), (6.6, 9.9),
                              (9.9, 13.2)]), 1.0)
    fast = end_to_end(window([(0, 3.2), (3.2, 6.4), (6.4, 9.6),
                              (9.6, 12.8)]), 1.0)
    assert fast["delta_rows_per_s"] > slow["delta_rows_per_s"]
    assert np.isclose(fast["delta_rows_per_s"], 4096 / 3.2)


def test_held_source_hands_out_nothing_more():
    recs = [DeltaRecord(np.array([i], np.int32), {"w": np.zeros((1, 2))},
                        np.array([1], np.int8), epoch=i) for i in range(6)]
    src = ScheduledSource(recs)
    src.release(0, np.zeros(6))
    assert len(src.poll(3)) == 3
    assert src.hold() == 2
    assert src.poll(10) == [] and src.next_due() == np.inf

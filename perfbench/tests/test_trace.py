"""The reduction from a profiler trace to busy time, program time,
operations and named idle gaps."""
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench.harness import trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_gaps():
    iv = np.array([[0, 2], [1, 3], [5, 6], [10, 11]], float)
    assert trace.union_length(iv) == 5.0
    assert trace.idle_gaps(iv, 0.5, 10.5) == [(3.0, 5.0), (6.0, 10.0)]
    assert trace.union_length(trace.clip(iv, 0.5, 10.5)) == 4.0


def test_reduce_names_gaps_by_host_span():
    dev = {"XLA Ops": [("sort", 0.0, 1.0), ("fusion", 1.0, 1.5),
                       ("sort", 4.0, 5.0)],
           "XLA Modules": [("jit__merge_reduce(1)", 0.0, 1.5),
                           ("jit__merge_reduce(1)", 4.0, 5.0)]}
    spans = [("refresh", 0.0, 2.0), ("fetch", 2.0, 2.5),
             ("step", 2.5, 6.0), ("poll", 2.6, 3.9)]
    s = trace.reduce_events([dev], 0.0, 6.0, spans)
    assert s.busy_s == 2.5 and s.window_s == 6.0 and s.devices == 1
    assert s.modules == {"jit__merge_reduce(1)": 2.5}
    assert s.ops[0] == ("sort", 2.0)
    assert s.gaps == [("poll", 2.5), ("step", 1.0)]


@pytest.mark.skipif(not (DATA / "small.xplane.pb").exists(),
                    reason="no recorded trace")
def test_recorded_chip_trace():
    meta = json.loads((DATA / "small.json").read_text())
    s = trace.summarize(DATA, meta["t0"], meta["closed"], meta["spans"])
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s == pytest.approx(meta["closed"])
    sort_s = sum(v for k, v in s.modules.items() if "small_sort" in k)
    # a program's envelope spans its operations and the few ns between
    assert 0 < s.busy_s <= sort_s <= s.busy_s * 1.001
    # three sleeps of 20 ms inside poll spans are the longest gaps
    assert [g[0] for g in s.gaps[:3]] == ["poll"] * 3
    assert all(0.015 < g[1] < 0.05 for g in s.gaps[:3])

"""The reduction from a profiler trace to idle time by the program's host
spans and merge device time by the named scopes of its stages."""
import json
from pathlib import Path

import pytest

from perfbench.harness import phases, trace

DATA = Path(__file__).resolve().parent / "data"
PB2 = phases.load_xplane_pb2()


def test_idle_goes_to_the_innermost_span():
    spans = [("repro.stream.step", 0.0, 10.0),
             ("repro.stream.prepare", 0.5, 2.0),
             ("repro.session.update", 2.0, 9.0),
             ("repro.incremental.merge", 3.0, 5.0),
             ("repro.mrbg_store.append", 6.0, 8.0)]
    gaps = [(1.0, 2.5), (4.0, 4.5), (5.5, 6.5), (9.5, 11.0)]
    got = phases.idle_by_span(gaps, spans)
    assert got == pytest.approx({
        "repro.stream.prepare": 1.0,
        "repro.session.update": 0.5 + 0.5,
        "repro.incremental.merge": 0.5,
        "repro.mrbg_store.append": 0.5,
        "repro.stream.step": 0.5,
        "untraced": 1.0})
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in gaps))
    assert phases.idle_by_span([(0.0, 1.0)], []) == {"untraced": 1.0}


def test_device_time_by_scope_of_the_merge_program():
    dev = {"modules": [("jit__merge_reduce(7)", 0.0, 4.0),
                       ("jit__delta_map(3)", 5.0, 6.0)],
           "ops": [("jit(_merge_reduce)/shuffle_reduce/sort/jit(sorted_"
                    "lanes)/pallas_call:", 0.0, 1.0),
                   ("jit(_merge_reduce)/shuffle_reduce/route/while:",
                    1.0, 2.5),
                   ("jit(_merge_reduce)/shuffle_reduce/reduce/add:",
                    2.5, 3.0),
                   ("jit(_merge_reduce)/broadcast_in_dim:", 3.0, 3.5),
                   ("jit(_delta_map)/shuffle_reduce/sort/sort:", 5.0, 6.0)]}
    got = phases.device_by_scope([dev, dev], 0.5, 10.0)
    assert got == pytest.approx({"shuffle_reduce/sort": 0.5,
                                 "shuffle_reduce/route": 1.5,
                                 "shuffle_reduce/reduce": 0.5,
                                 "unscoped": 0.5})
    assert phases.device_by_scope([dev], 0.0, 1.0, "_delta_map") == {}


def test_nested_operations_count_once():
    """A loop's event holds its body's operations: each counts its own
    time, and the loop, which has no tf_op, takes its body's scope."""
    body = "jit(_merge_reduce)/shuffle_reduce/route/jit(searchsorted)/while"
    dev = {"modules": [("jit__merge_reduce(7)", 0.0, 10.0)],
           "ops": [("jit(_merge_reduce)/shuffle_reduce/sort/sort:",
                    0.0, 2.0),
                   ("", 2.0, 8.0),                      # the while loop
                   (body + "/body/closed_call/gather:", 2.0, 4.5),
                   ("", 4.5, 4.5),
                   (body + "/body/closed_call/gather:", 5.0, 7.5),
                   ("", 8.0, 9.0)]}                     # held by nothing
    got = phases.device_by_scope([dev], 0.0, 10.0)
    assert got == pytest.approx({"shuffle_reduce/sort": 2.0,
                                 "shuffle_reduce/route": 6.0,
                                 "unscoped": 1.0})


def test_span_names_drop_their_encoded_stats():
    assert phases.span_name("repro.incremental.merge#epoch=12#") == \
        "repro.incremental.merge"
    assert phases.span_name("repro.stream.step") == "repro.stream.step"


@pytest.mark.skipif(PB2 is None, reason="no XSpace reader (xplane_pb2)")
def test_tf_op_of_the_recorded_chip_trace():
    devs = phases.device_scoped_ops(DATA / "small.xplane.pb", PB2)
    assert len(devs) == 1
    assert "jit(small_sort)/jit(sort)/sort:" in {t for t, _, _ in
                                                 devs[0]["ops"]}
    assert all("small_sort" in name for name, _, _ in devs[0]["modules"])
    # the raw protobuf and ProfileData see the same operations, same clock
    _, pd = trace.load_xplane(DATA / "small.xplane.pb")
    ops = pd[0][trace.OPS_LINE]
    assert len(ops) == len(devs[0]["ops"])
    assert all(abs(a[1] - b[1]) < 2e-9 for a, b in zip(ops, devs[0]["ops"]))


@pytest.mark.skipif(not (DATA / "scoped.xplane.pb").exists(),
                    reason="no recorded scoped trace")
def test_recorded_scoped_chip_trace():
    meta = json.loads((DATA / "scoped.json").read_text())
    got = phases.summarize(DATA / "scoped.xplane.pb", meta["closed"],
                           meta["program"])
    idle = got.idle_by_span
    # three 20 ms sleeps in their spans, 10 ms after the last outside any
    assert 0.055 < idle["repro.test.sleep"] < 0.1
    assert 0.008 < idle["untraced"] < 0.03
    assert set(idle) <= {"repro.test.sleep", "untraced", "repro.test.step",
                         "repro.test.device"}
    anchor, devices = trace.load_xplane(DATA / "scoped.xplane.pb")
    summary = trace.reduce_events(devices, anchor, anchor + meta["closed"],
                                  [])
    assert sum(idle.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-6)
    if PB2 is None:
        assert got.device_by_scope is None
        return
    scopes = got.device_by_scope
    assert scopes["shuffle_reduce/sort"] > 0
    assert scopes["shuffle_reduce/route"] > 0
    # the sort, then the search's loop and the body operations it holds,
    # each counted once: no more than the program's runs, and nearly all
    # of their busy time
    program = sum(s for k, s in summary.modules.items() if "scoped" in k)
    assert 0.99 * summary.busy_s <= sum(scopes.values()) <= program * 1.001
    assert scopes["shuffle_reduce/route"] > 10 * scopes["shuffle_reduce/sort"]
    assert scopes.get("unscoped", 0.0) < 0.05 * sum(scopes.values())

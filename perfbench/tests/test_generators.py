"""The copied generators are deterministic for a seed, and the plain
references agree with the engine's own oracles at a small size."""
import numpy as np
import pytest

from perfbench.harness import schedule
from perfbench.tests.conftest import JOBS, job
from repro.apps import pagerank as pr, wordcount as wc

KINDS = sorted(JOBS)


def event_arrays(j, n):
    recs = j.records(n)
    return [(r.record_ids, next(iter(r.values.values())), r.sign, r.epoch)
            for r in recs]


@pytest.mark.parametrize("kind", KINDS)
def test_same_seed_same_data_and_events(kind):
    big = 2**31 + 12345
    a, b, c = job(kind, big), job(kind, big), job(kind, big + 1)
    da, db, dc = (np.asarray(next(iter(x.data.values.values())))
                  for x in (a, b, c))
    assert np.array_equal(da, db) and not np.array_equal(da, dc)
    ea, eb = event_arrays(a, 50), event_arrays(b, 50)
    for (ra, va, sa, pa), (rb, vb, sb, pb) in zip(ea, eb):
        assert np.array_equal(ra, rb) and np.array_equal(va, vb)
        assert np.array_equal(sa, sb) and pa == pb


def test_poisson_schedule_same_gaps_other_order():
    t = {"arrivals": "poisson", "events_per_s": 50, "gap_seed": 7}
    a = schedule.window_offsets(t, 10, 1)
    b = schedule.window_offsets(t, 10, 1)
    c = schedule.window_offsets(t, 10, 2)
    assert np.array_equal(a, b) and len(a) == len(c) == 500
    assert not np.array_equal(a, c)
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(c, prepend=0)))
    assert a[-1] < 10 and np.all(np.diff(a) >= 0)
    assert np.array_equal(schedule.window_offsets(
        {"arrivals": "backlog", "events": 9}, 10, 1), np.zeros(9))


def test_wordcount_events_and_reference():
    j = job("wordcount", 3)
    recs = j.records(200)
    mirror = j.docs0.copy()
    for r in recs:                      # '-' carries the current tokens
        d = r.record_ids[0]
        assert np.array_equal(r.values["w"][0], mirror[d])
        mirror[d] = r.values["w"][1]
    assert np.array_equal(j.corpus(200), mirror)
    for k in (0, 57, 200):
        assert np.array_equal(j.reference(k)["c"],
                              wc.oracle(j.corpus(k), j.vocab))


def test_records_as_randomtextwriter_writes_them():
    j = job("wordcount", 5, documents=4096, min_words=10, max_words=100,
            vocab=1000)
    lengths = (j.docs0 >= 0).sum(axis=1)
    assert j.docs0.shape == (4096, 99)
    assert lengths.min() == 10 and lengths.max() == 99
    assert np.all((j.docs0 >= 0) == (np.arange(99) < lengths[:, None]))
    assert abs(lengths.mean() - 54.5) < 1.0
    counts = np.bincount(j.docs0[j.docs0 >= 0], minlength=1000)
    assert len(counts) == 1000
    # uniform words: every count near the mean, by a chi-square bound
    chi2 = ((counts - counts.mean()) ** 2 / counts.mean()).sum()
    assert chi2 < 1200


def test_permutation_choice_rewrites_each_document_once_a_cycle():
    j = job("wordcount", 6, documents=64)
    recs = j.records(200)
    docs = np.array([r.record_ids[0] for r in recs])
    for c in range(3):
        assert sorted(docs[64 * c:64 * (c + 1)]) == list(range(64))
    mirror = j.docs0.copy()
    for r in recs:
        d = r.record_ids[0]
        assert np.array_equal(r.values["w"][0], mirror[d])
        mirror[d] = r.values["w"][1]
    assert np.array_equal(j.corpus(200), mirror)


def test_rmat_graph_and_rewires():
    j = job("pagerank", 4)
    nbrs = j.nbrs0
    for v in range(j.n):
        row = nbrs[v][nbrs[v] >= 0]
        assert v not in row and len(set(row)) == len(row)
    recs = j.records(100)
    for r in recs:
        old, new = r.values["nbrs"]
        v = r.record_ids[0]
        assert (old >= 0).sum() == (new >= 0).sum() > 0
        live = new[new >= 0]
        assert v not in live and len(set(live)) == len(live)
    assert np.array_equal(j.reference(100)["r"] > 0, np.ones(j.n, bool))
    for k in (0, 100):
        want = pr.oracle(j.graph(k), iters=3000, tol=1e-13)
        assert np.allclose(j.reference(k)["r"], want, rtol=1e-10)

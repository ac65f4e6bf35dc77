"""Per-app correctness vs dense numpy oracles (full + incremental)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.incr_iter import IncrIterJob
from repro.core.incremental import make_delta
from repro.core.iterative import run_iterative, run_plain


def _update_delta(rows, olds, news, key):
    n = len(rows)
    dk = np.repeat(np.asarray(rows, np.int32), 2)
    sg = np.tile(np.array([-1, 1], np.int8), n)
    buf = np.empty((2 * n,) + olds.shape[1:], olds.dtype)
    buf[0::2] = olds
    buf[1::2] = news
    return make_delta(dk, {key: jnp.asarray(buf)}, sg)


class TestPageRank:
    def test_converges_to_oracle(self):
        from repro.apps import pagerank as pr
        nbrs = pr.random_graph(128, 5, seed=1)
        st, hist = run_iterative(pr.make_spec(128), pr.make_struct(nbrs),
                                 max_iters=150, tol=1e-8)
        want = pr.oracle(nbrs)
        np.testing.assert_allclose(np.asarray(st.values["r"]), want,
                                   atol=1e-4)

    def test_plain_equals_iter(self):
        from repro.apps import pagerank as pr
        nbrs = pr.random_graph(64, 4, seed=2)
        s1, _ = run_iterative(pr.make_spec(64), pr.make_struct(nbrs),
                              max_iters=80, tol=1e-7)
        s2, _ = run_plain(pr.make_spec(64), pr.make_struct(nbrs),
                          max_iters=80, tol=1e-7)
        np.testing.assert_allclose(np.asarray(s1.values["r"]),
                                   np.asarray(s2.values["r"]), atol=1e-6)


class TestSSSP:
    def test_converges_to_bellman_ford(self):
        from repro.apps import sssp
        nbrs, w = sssp.random_weighted_graph(96, 5, seed=2, p_edge=0.35)
        st, _ = run_iterative(sssp.make_spec(96),
                              sssp.make_struct(nbrs, w, src=0),
                              max_iters=150, tol=1e-7)
        want = sssp.oracle(nbrs, w, 0)
        got = np.asarray(st.values["d"])
        finite = want < sssp.INF / 2
        np.testing.assert_allclose(got[finite], want[finite], atol=1e-3)
        assert (got[~finite] > sssp.INF / 2).all()

    def test_incremental_edge_deletion_increases_distances(self):
        """min-reduce requires the MRBGraph: deletions must propagate
        distance *increases* — impossible for accumulator shortcuts."""
        from repro.apps import sssp
        nbrs, w = sssp.random_weighted_graph(96, 5, seed=2, p_edge=0.35)
        spec = sssp.make_spec(96)
        job = IncrIterJob(spec, sssp.make_struct(nbrs, w, src=0),
                          value_bytes=4)
        job.initial_converge(max_iters=150, tol=1e-7)
        rows = np.array([3, 11], np.int32)
        new_n = nbrs[rows].copy()
        new_n[:, :2] = -1
        # record id = vertex + 1 (row 0 is the virtual root)
        dk = np.repeat(rows + 1, 2)
        sg = np.tile(np.array([-1, 1], np.int8), 2)
        nb = np.empty((4,) + nbrs.shape[1:], nbrs.dtype)
        nb[0::2] = nbrs[rows]
        nb[1::2] = new_n
        wb = np.repeat(w[rows], 2, axis=0)
        delta = make_delta(dk, {"nbrs": jnp.asarray(nb),
                                 "w": jnp.asarray(wb)}, sg)
        st, hist = job.refresh(delta, max_iters=150, tol=1e-7,
                               cpc_threshold=0.0)
        nbrs2 = nbrs.copy()
        nbrs2[rows] = new_n
        want = sssp.oracle(nbrs2, w, 0)
        got = np.asarray(st.values["d"])
        finite = want < sssp.INF / 2
        np.testing.assert_allclose(got[finite], want[finite], atol=1e-3)
        assert (got[~finite] > sssp.INF / 2).all()


class TestKmeans:
    def test_converges_to_oracle(self):
        from repro.apps import kmeans
        rng = np.random.default_rng(0)
        k, dim = 4, 3
        centers = rng.normal(0, 5, (k, dim))
        pts = np.concatenate(
            [rng.normal(c, 0.3, (50, dim)) for c in centers]
        ).astype(np.float32)
        init = pts[rng.choice(len(pts), k, replace=False)]
        st, _ = run_iterative(kmeans.make_spec(k, dim, init),
                              kmeans.make_struct(pts), max_iters=50,
                              tol=1e-6)
        want = kmeans.oracle(pts, init)
        got = np.sort(np.asarray(st.values["c"]), axis=0)
        np.testing.assert_allclose(got, np.sort(want, axis=0), atol=1e-3)


class TestGIMV:
    def test_converges_to_dense_fixpoint(self):
        from repro.apps import gimv
        nb, bs = 8, 16
        blocks = gimv.random_blocks(nb, bs, seed=4)
        bvec = np.ones((nb, bs), np.float32)
        st, _ = run_iterative(gimv.make_spec(nb, bs, bvec),
                              gimv.make_struct(blocks, nb),
                              max_iters=300, tol=1e-9)
        want = gimv.oracle(blocks, nb, bs, bvec)
        np.testing.assert_allclose(np.asarray(st.values["v"]), want,
                                   atol=1e-4)

    def test_incremental_block_update(self):
        from repro.apps import gimv
        nb, bs = 8, 8
        blocks = gimv.random_blocks(nb, bs, seed=5)
        bvec = np.ones((nb, bs), np.float32)
        spec = gimv.make_spec(nb, bs, bvec)
        job = IncrIterJob(spec, gimv.make_struct(blocks, nb),
                          value_bytes=4 * bs)
        job.initial_converge(max_iters=300, tol=1e-9)
        rids = np.array([5], np.int32)
        newb = blocks.copy()
        newb[5] = blocks[5] * 0.25
        delta = _update_delta(rids, blocks[rids], newb[rids], "m")
        st, hist = job.refresh(delta, max_iters=300, tol=1e-9,
                               cpc_threshold=0.0)
        want = gimv.oracle(newb, nb, bs, bvec)
        np.testing.assert_allclose(np.asarray(st.values["v"]), want,
                                   atol=1e-4)


class TestAPriori:
    def test_accumulator_matches_oracle(self):
        from repro.apps import apriori
        from repro.core.accumulator import AccumulatorJob
        rng = np.random.default_rng(1)
        V, L, N = 40, 10, 150
        tweets = rng.integers(0, V, (N, L)).astype(np.int32)
        tweets[rng.random((N, L)) < 0.2] = -1
        pairs = apriori.candidate_pairs(tweets, V, top=24)
        job = AccumulatorJob(apriori.make_spec(pairs))
        job.initial_run(apriori.make_input(np.arange(N), tweets))
        new = rng.integers(0, V, (20, L)).astype(np.int32)
        ids = np.arange(N, N + 20, dtype=np.int32)
        delta = make_delta(ids, {"w": jnp.asarray(new)},
                           np.ones(20, np.int8))
        job.incremental_run(delta)
        want = apriori.oracle(np.concatenate([tweets, new]), pairs)
        np.testing.assert_allclose(job.view.as_dict()["c"], want)


# ---------------------------------------------------------------------------
# the vectorized NumPy oracles vs plain per-record loops of the same semantics
# ---------------------------------------------------------------------------

def _wordcount_loop(docs, vocab, valid):
    counts = np.zeros(vocab)
    for i, d in enumerate(docs):
        if valid[i]:
            for w in d:
                if w >= 0:
                    counts[w] += 1
    return counts


def _pagerank_loop(nbrs, valid, damping):
    s = nbrs.shape[0]
    r = np.ones(s)
    for _ in range(200):
        acc = np.zeros(s)
        for i in range(s):
            out = nbrs[i][nbrs[i] >= 0]
            if valid[i] and out.size:
                np.add.at(acc, out, r[i] / out.size)
        new = damping * acc + (1 - damping)
        done = np.abs(new - r).max() < 1e-12
        r = new
        if done:
            break
    return r


def _sssp_loop(nbrs, w, src, valid, inf):
    """Bellman-Ford relaxing in place, edge by edge."""
    s = nbrs.shape[0]
    d = np.full(s, np.float64(inf))
    d[src] = 0.0
    changed = True
    while changed:
        changed = False
        for i in range(s):
            if not valid[i] or d[i] >= inf / 2:
                continue
            for jj, jv in enumerate(nbrs[i]):
                if jv >= 0 and d[i] + w[i, jj] < d[jv] - 1e-12:
                    d[jv] = d[i] + w[i, jj]
                    changed = True
    return d


@pytest.mark.parametrize("app", ["wordcount", "pagerank", "sssp"])
def test_oracle_matches_loop_reference(app):
    from repro.apps import pagerank as pr, sssp, wordcount as wc
    rng = np.random.default_rng(4)
    n = 120
    valid = rng.random(n) < 0.9
    if app == "wordcount":
        docs = rng.integers(-1, 30, (n, 6)).astype(np.int32)
        np.testing.assert_array_equal(wc.oracle(docs, 30, valid),
                                      _wordcount_loop(docs, 30, valid))
    elif app == "pagerank":
        nbrs = pr.random_graph(n, 5, seed=3)
        np.testing.assert_allclose(pr.oracle(nbrs, valid),
                                   _pagerank_loop(nbrs, valid, pr.DAMPING),
                                   rtol=1e-12)
    else:
        nbrs, w = sssp.random_weighted_graph(n, 4, seed=3, p_edge=0.4)
        np.testing.assert_allclose(sssp.oracle(nbrs, w, 0, valid),
                                   _sssp_loop(nbrs, w, 0, valid, sssp.INF),
                                   rtol=1e-12)

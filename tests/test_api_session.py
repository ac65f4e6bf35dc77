"""repro.api façade: one Session drives every paper mode, with parity
against the internal (pre-refactor) entry points on identical inputs."""
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

from repro.api import (
    IterSpec, RunConfig, Session, default_difference, make_delta,
)
from repro.apps import kmeans, pagerank as pr, wordcount as wc
from repro.core.accumulator import AccumulatorJob
from repro.core.incr_iter import IncrIterJob
from repro.core.incremental import IncrementalJob
from repro.core.iterative import run_iterative

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _wc_corpus(n=30, vocab=60, length=8, seed=0):
    rng = np.random.default_rng(seed)
    docs = rng.integers(0, vocab, size=(n, length)).astype(np.int32)
    docs[rng.random(docs.shape) < 0.1] = -1
    return docs


def _update_delta(rows, old_rows, new_rows, values_key="w"):
    n = len(rows)
    rid = np.repeat(np.asarray(rows, np.int32), 2)
    sg = np.tile(np.array([-1, 1], np.int8), n)
    buf = np.empty((2 * n,) + old_rows.shape[1:], old_rows.dtype)
    buf[0::2] = old_rows
    buf[1::2] = new_rows
    return make_delta(rid, {values_key: jnp.asarray(buf)}, sg)


# ---------------------------------------------------------------------------
# mode 1+2: one-step and incremental one-step
# ---------------------------------------------------------------------------

class TestOneStep:
    VOCAB = 60

    def test_parity_with_incremental_job(self):
        """Session(mrbg) == IncrementalJob on the same input and delta."""
        docs = _wc_corpus()
        rng = np.random.default_rng(1)
        new3 = rng.integers(0, self.VOCAB, (1, docs.shape[1])).astype(np.int32)
        delta = _update_delta([3], docs[[3]], new3)

        spec, data = wc.make_job(docs, self.VOCAB)
        sess = Session(spec, RunConfig(onestep_path="mrbg", value_bytes=4))
        rep0 = sess.run(data)
        rep1 = sess.update(delta)

        old = IncrementalJob(wc.make_spec(self.VOCAB), value_bytes=4)
        old.initial_run(wc.make_input(np.arange(len(docs)), docs))
        old.incremental_run(delta)

        np.testing.assert_array_equal(sess.result["c"],
                                      old.view.as_dict()["c"])
        assert rep0.mode == "onestep" and rep1.mode == "incremental"
        assert rep1.affected_keys > 0
        assert rep1.io is not None

    def test_accumulator_auto_path_agrees(self):
        """onestep_path='auto' picks the §3.5 accumulator for sum reducers
        and produces the same refreshed output as the MRBG engine."""
        docs = _wc_corpus()
        rng = np.random.default_rng(2)
        new5 = rng.integers(0, self.VOCAB, (1, docs.shape[1])).astype(np.int32)
        delta = _update_delta([5], docs[[5]], new5)

        spec, data = wc.make_job(docs, self.VOCAB)
        auto = Session(spec, RunConfig())          # auto -> accumulator
        auto.run(data)
        rep = auto.update(delta)
        assert rep.mode == "accumulator"

        old = AccumulatorJob(wc.make_spec(self.VOCAB))
        old.initial_run(wc.make_input(np.arange(len(docs)), docs))
        old.incremental_run(delta)
        np.testing.assert_array_equal(auto.result["c"],
                                      old.view.as_dict()["c"])

        docs2 = docs.copy()
        docs2[5] = new5[0]
        np.testing.assert_allclose(auto.result["c"],
                                   wc.oracle(docs2, self.VOCAB))


# ---------------------------------------------------------------------------
# mode 3: plain / iterative recomputation
# ---------------------------------------------------------------------------

class TestIterative:
    def test_parity_with_run_iterative(self):
        nbrs = pr.random_graph(128, 4, seed=7, p_edge=0.5)
        spec, struct = pr.make_job(nbrs)
        sess = Session(spec, RunConfig(max_iters=80, tol=1e-7))
        rep = sess.run(struct)

        state, hist = run_iterative(pr.make_spec(128), pr.make_struct(nbrs),
                                    max_iters=80, tol=1e-7)
        assert rep.mode == "iterative"
        assert rep.iters == hist["iters"]
        np.testing.assert_allclose(sess.result["r"],
                                   np.asarray(state.values["r"]),
                                   rtol=1e-6, atol=0)

    def test_plain_shuffle_same_results(self):
        """RunConfig(plain_shuffle=True) is the Algorithm-5 cost model:
        identical math, so results match the warm loop exactly."""
        nbrs = pr.random_graph(96, 4, seed=9, p_edge=0.5)
        spec, struct = pr.make_job(nbrs)
        warm = Session(spec, RunConfig(max_iters=60, tol=1e-7))
        warm.run(struct)
        spec2, struct2 = pr.make_job(nbrs)
        plain = Session(spec2, RunConfig(max_iters=60, tol=1e-7,
                                         plain_shuffle=True))
        rep = plain.run(struct2)
        assert rep.mode == "plainMR"
        np.testing.assert_allclose(plain.result["r"], warm.result["r"],
                                   rtol=1e-6, atol=0)


# ---------------------------------------------------------------------------
# mode 4: incremental iterative (+ §5.2 auto MRBG-off)
# ---------------------------------------------------------------------------

class TestIncrementalIterative:
    def test_parity_with_incr_iter_job(self):
        S, F = 512, 4
        nbrs = pr.random_graph(S, F, seed=3, p_edge=0.5)
        rng = np.random.default_rng(5)
        rows = rng.choice(S, 5, replace=False)
        new_rows = np.where(rng.random((5, F)) < 0.5,
                            rng.integers(0, S, (5, F)), -1).astype(np.int32)
        delta = _update_delta(rows, nbrs[rows], new_rows, "nbrs")

        spec, struct = pr.make_job(nbrs)
        sess = Session(spec, RunConfig(max_iters=150, tol=1e-7,
                                       value_bytes=4))
        sess.run(struct)
        rep = sess.update(delta)

        old = IncrIterJob(pr.make_spec(S), pr.make_struct(nbrs),
                          value_bytes=4)
        old.initial_converge(max_iters=150, tol=1e-7)
        st, hist = old.refresh(delta, max_iters=150, tol=1e-7)

        assert rep.mode == hist["mode"]
        assert rep.iters == hist["iters"]
        np.testing.assert_allclose(sess.result["r"],
                                   np.asarray(st.values["r"]),
                                   rtol=1e-6, atol=0)
        # refresh telemetry flows through the uniform report
        if rep.mode == "i2":
            assert rep.affected_keys == sum(
                l.n_affected_dks for l in hist["logs"])
            assert rep.io is not None

    def test_auto_mrbg_off_kmeans(self):
        """The Session decides the §5.2 fallback internally (paper Fig. 8:
        Kmeans always lands in iterMR recomp mode)."""
        rng = np.random.default_rng(0)
        k, dim = 3, 2
        centers = rng.normal(0, 6, (k, dim))
        pts = np.concatenate(
            [rng.normal(c, 0.3, (30, dim)) for c in centers]
        ).astype(np.float32)
        init = pts[rng.choice(len(pts), k, replace=False)]
        spec, struct = kmeans.make_job(pts, init)
        sess = Session(spec, RunConfig(max_iters=50, tol=1e-6,
                                       value_bytes=4 * (dim + 1)))
        sess.run(struct)
        new = rng.normal(centers[0], 0.3, (3, dim)).astype(np.float32)
        rep = sess.update(_update_delta([0, 1, 2], pts[:3], new, "p"))
        assert rep.mode == "iterMR-fallback"
        assert sess.result["c"].shape == (k, dim)


# ---------------------------------------------------------------------------
# mode 5: distributed via RunConfig(mesh=...) — needs 8 XLA host devices,
# so the parity run happens in a subprocess (flag must precede jax init)
# ---------------------------------------------------------------------------

def test_distributed_via_config_parity():
    script = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.api import Session, RunConfig, MeshConfig, make_delta
from repro.apps import pagerank as pr

S, F = 256, 5
nbrs = pr.random_graph(S, F, seed=11, p_edge=0.5)
spec, struct = pr.make_job(nbrs)
mesh = Mesh(np.array(jax.devices()[:8]), ("data",))

# the pre-PR-7 flat spelling was removed after its one-release
# deprecation window: bare Mesh now fails fast with a pointer to
# MeshConfig, and the flat knobs are unknown kwargs
try:
    RunConfig(mesh=mesh, max_iters=60)
except TypeError as e:
    assert "MeshConfig" in str(e), e
else:
    raise AssertionError("bare Mesh accepted")
try:
    RunConfig(mesh=MeshConfig(mesh, axis="data"), shuffle_cap=512)
except TypeError:
    pass
else:
    raise AssertionError("flat shuffle_cap accepted")

cfg = RunConfig(mesh=MeshConfig(mesh, axis="data", shuffle_cap=512),
                max_iters=60, tol=1e-7)
assert cfg.mesh.shuffle_cap == 512
sess = Session(spec, cfg)
rep = sess.run(struct)
assert rep.mode == "distributed", rep.mode

from repro.core.distributed import (partition_struct, partition_state,
                                    unpartition_state, run_distributed)
skeys, svals, svalid = partition_struct(
    spec, np.arange(S, dtype=np.int32), {"nbrs": nbrs},
    np.ones(S, bool), 8, sess._driver._partition_cap())
state0 = partition_state({"r": np.ones(S, np.float32)}, S, 8)
out, hist = run_distributed(spec, mesh, (skeys, svals, svalid), state0,
                            axis="data", shuffle_cap=512, max_iters=60,
                            tol=1e-7)
ref = unpartition_state({k: np.asarray(v) for k, v in out.items()}, S)

np.testing.assert_array_equal(sess.result["r"], ref["r"])
assert rep.iters == hist["iters"]

# refresh: delta -> repartition -> warm re-converge, all inside update()
rng = np.random.default_rng(5)
rows = rng.choice(S, 4, replace=False)
new = np.where(rng.random((4, F)) < 0.5,
               rng.integers(0, S, (4, F)), -1).astype(np.int32)
rid = np.repeat(rows.astype(np.int32), 2)
buf = np.empty((8, F), np.int32); buf[0::2] = nbrs[rows]; buf[1::2] = new
delta = make_delta(rid, {"nbrs": jnp.asarray(buf)},
                   np.tile(np.array([-1, 1], np.int8), 4))
rep = sess.update(delta)
nbrs2 = nbrs.copy(); nbrs2[rows] = new
want = pr.oracle(nbrs2, iters=300)
rel = np.abs(sess.result["r"] - want) / np.maximum(want, 1e-9)
assert rel.max() < 1e-3, rel.max()
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices; never a chip
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_distributed_accepts_onestep_rejects_replicated():
    from repro.api import MeshConfig
    from repro.core.engine import JobSpec
    from repro.core.kvstore import sum_reducer

    class FakeMesh:                     # stands in for a Mesh; never used
        shape = {"data": 2}

    # JobSpec + mesh drives the per-shard one-step engine
    sess = Session(JobSpec(lambda kv, s: None, sum_reducer(), 4, "j"),
                   RunConfig(mesh=MeshConfig(FakeMesh())))
    assert sess._driver.kind == "distributed-onestep"
    spec = kmeans.make_spec(2, 2, np.zeros((2, 2), np.float32))
    with pytest.raises(ValueError, match="replicate_state"):
        Session(spec, RunConfig(mesh=MeshConfig(FakeMesh())))


# ---------------------------------------------------------------------------
# API ergonomics and satellite fixes
# ---------------------------------------------------------------------------

def test_make_delta_keys_default_to_record_ids():
    d = make_delta([1, 2], {"w": jnp.zeros((2, 3))}, [1, 1])
    np.testing.assert_array_equal(np.asarray(d.keys),
                                  np.asarray(d.record_ids))
    np.testing.assert_array_equal(np.asarray(d.keys), [1, 2])
    assert bool(np.all(np.asarray(d.valid)))


def test_make_delta_legacy_order_rejected():
    # the pre-repro.api positional order (keys, record_ids, values, sign)
    # was shimmed for one release; keys/valid are now keyword-only
    with pytest.raises(TypeError):
        make_delta([9, 9], [1, 2], {"w": jnp.zeros((2, 3))}, [-1, 1])
    d = make_delta([1, 2], {"w": jnp.zeros((2, 3))}, [-1, 1], keys=[9, 9])
    np.testing.assert_array_equal(np.asarray(d.keys), [9, 9])
    np.testing.assert_array_equal(np.asarray(d.record_ids), [1, 2])
    np.testing.assert_array_equal(np.asarray(d.sign), [-1, 1])


def test_iterspec_difference_resolves_to_default():
    spec = IterSpec(map_fn=lambda s, d, g: None, reducer=None,
                    project=lambda sk: sk, num_state=4,
                    init_state=lambda dks: {"v": jnp.zeros(4)})
    assert spec.difference is default_difference
    # explicit differences are untouched
    f = lambda c, p: c["v"] - p["v"]
    spec2 = IterSpec(map_fn=lambda s, d, g: None, reducer=None,
                     project=lambda sk: sk, num_state=4,
                     init_state=lambda dks: {"v": jnp.zeros(4)},
                     difference=f)
    assert spec2.difference is f


def test_session_lifecycle_errors():
    docs = _wc_corpus(n=8)
    spec, data = wc.make_job(docs, 60)
    sess = Session(spec)
    with pytest.raises(RuntimeError, match="before run"):
        sess.update(make_delta([0], {"w": jnp.zeros((1, 8), jnp.int32)}, [1]))
    with pytest.raises(RuntimeError, match="no result"):
        sess.result
    sess.run(data)
    with pytest.raises(RuntimeError, match="already executed"):
        sess.run(data)


def test_old_entry_points_do_not_warn():
    """The one-release deprecation window is over: the internal entry
    points are plain functions again (no shim, no DeprecationWarning)."""
    import warnings
    docs = _wc_corpus(n=8)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        from repro.core.engine import run_onestep
        run_onestep(wc.make_spec(60), wc.make_input(np.arange(8), docs))


def test_every_app_has_make_job():
    """The uniform app convention: make_job(...) -> (spec, data)."""
    from repro.apps import apriori, gimv, sssp
    from repro.core.engine import JobSpec as JS

    rng = np.random.default_rng(0)
    docs = rng.integers(0, 20, (6, 4)).astype(np.int32)
    tweets = rng.integers(0, 20, (6, 4)).astype(np.int32)
    pairs = apriori.candidate_pairs(tweets, 20, top=4)
    nbrs = pr.random_graph(8, 2, seed=0)
    wnbrs, w = sssp.random_weighted_graph(8, 2, seed=0)
    blocks = gimv.random_blocks(2, 4, seed=0)
    pts = rng.normal(0, 1, (9, 2)).astype(np.float32)

    jobs = [wc.make_job(docs, 20), apriori.make_job(tweets, pairs),
            pr.make_job(nbrs), sssp.make_job(wnbrs, w, src=0),
            kmeans.make_job(pts, pts[:2]),
            gimv.make_job(blocks, 2, 4, np.ones((2, 4), np.float32))]
    for spec, data in jobs:
        assert isinstance(spec, (JS, IterSpec))
        assert data.capacity > 0
        Session(spec)                    # every job is Session-constructible

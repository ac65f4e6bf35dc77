"""Distributed fine-grain refresh: per-shard MRBG slices + delta exchange.

The contract under test is *bit-for-bit* parity: a meshed ``Session`` must
produce exactly the single-device result — on the initial converge, on
every ``update()``, and through CPC filtering and the §5.2 fallback — not
merely agree to a tolerance.  That only holds because the distributed step
sorts received edges by (K2, MK) before reducing, so per-key float
accumulation order matches the single-device shuffle.

Multi-device tests need >1 XLA host device, so they run in subprocesses
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the flag must
precede jax init, which already happened in the pytest process).
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
BACKENDS = ("xla", "pallas")


def _run(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices; never a chip
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


PRELUDE = """
import numpy as np, jax
from jax.sharding import Mesh
from repro.api import Session, RunConfig, MeshConfig, make_delta
mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
"""

WC_PRELUDE = PRELUDE + """
from repro.apps import wordcount as wc
VOCAB, L = 32, 4
rng = np.random.default_rng(7)
docs = rng.integers(0, VOCAB, (64, L)).astype(np.int32)
spec, data = wc.make_job(docs, VOCAB)

def doc_delta(mirror, n_pairs):
    rows = rng.choice(len(mirror), size=n_pairs, replace=False)
    new = rng.integers(0, VOCAB, (n_pairs, L)).astype(np.int32)
    rid = np.repeat(rows.astype(np.int32), 2)
    buf = np.empty((2 * n_pairs, L), np.int32)
    buf[0::2] = mirror[rows]; buf[1::2] = new
    mirror[rows] = new
    return make_delta(rid, {"w": buf}, np.tile(np.int8([-1, 1]), n_pairs))
"""

PR_PRELUDE = PRELUDE + """
from repro.apps import pagerank as pr
S, F = 256, 5
nbrs = pr.random_graph(S, F, seed=11, p_edge=0.5)
spec, struct = pr.make_job(nbrs)

def graph_delta(mirror, n_rows):
    rows = rng.choice(S, n_rows, replace=False)
    new = np.where(rng.random((n_rows, F)) < 0.5,
                   rng.integers(0, S, (n_rows, F)), -1).astype(np.int32)
    rid = np.repeat(rows.astype(np.int32), 2)
    buf = np.empty((2 * n_rows, F), np.int32)
    buf[0::2] = mirror[rows]; buf[1::2] = new
    mirror[rows] = new
    return make_delta(rid, {"nbrs": buf},
                      np.tile(np.int8([-1, 1]), n_rows))
rng = np.random.default_rng(5)
"""


# ---------------------------------------------------------------------------
# bit-for-bit parity with the single-device engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_onestep_update_parity_bitwise(backend):
    """Wordcount run + fine updates on an 8-shard mesh == single device,
    exactly (integer counts leave no float slack to hide behind)."""
    _run(WC_PRELUDE + f"""
cfg = dict(backend="{backend}", value_bytes=4)
ref = Session(spec, RunConfig(**cfg)); ref.run(data)
dist = Session(spec, RunConfig(mesh=MeshConfig(mesh), **cfg))
rep = dist.run(data)
assert rep.mode == "distributed", rep.mode
np.testing.assert_array_equal(ref.result["c"], dist.result["c"])

mirror = docs.copy()
for pairs in (4, 12, 4):
    d = doc_delta(mirror, pairs)
    r1 = ref.update(d); r2 = dist.update(d)
    assert r2.mode == "distributed-incr", r2.mode
    np.testing.assert_array_equal(ref.result["c"], dist.result["c"])
    assert r2.shuffle.edges_exchanged > 0
    assert r2.shuffle.bytes_moved == r2.shuffle.edges_exchanged * 14
np.testing.assert_array_equal(dist.result["c"], wc.oracle(mirror, VOCAB))
print("OK")
""")


@pytest.mark.parametrize("backend", BACKENDS)
def test_iterative_cpc_update_parity_bitwise(backend):
    """Pagerank fine refresh (CPC filtering, no fallback) on the mesh is
    bit-for-bit the single-device i2 refresh, epoch after epoch.

    The xla backend is held to exact bits.  The pallas reduce kernels
    accumulate in buffer-shaped blocks, so the sharded layout shifts the
    float reduction tree by 1-2 ulp — there parity is held to one float32
    ulp of the converged rank mass instead.
    """
    exact = backend == "xla"
    _run(PR_PRELUDE + f"""
kw = dict(backend="{backend}", max_iters=60, tol=1e-7,
          cpc_threshold=5e-4, pdelta_threshold=1.0)
check = (np.testing.assert_array_equal if {exact!r}
         else lambda a, b: np.testing.assert_allclose(a, b, atol=5e-7))
ref = Session(spec, RunConfig(**kw)); ref.run(struct)
dist = Session(spec, RunConfig(mesh=MeshConfig(mesh, shuffle_cap=512), **kw))
dist.run(struct)
check(ref.result["r"], dist.result["r"])

mirror = nbrs.copy()
for _ in range(3):
    d = graph_delta(mirror, 4)
    r1 = ref.update(d); r2 = dist.update(d)
    assert (r1.mode, r2.mode) == ("i2", "distributed-i2"), (r1.mode, r2.mode)
    assert r1.iters == r2.iters
    check(ref.result["r"], dist.result["r"])
print("OK")
""")


def test_fallback_parity_bitwise():
    """When P_delta trips the §5.2 auto MRBG-off, the meshed session must
    fall back exactly like the single-device engine (same mode, same
    bits) and recover fine refresh after the re-seed."""
    _run(PR_PRELUDE + """
kw = dict(backend="xla", max_iters=60, tol=1e-7,
          cpc_threshold=5e-4, pdelta_threshold=0.05)
ref = Session(spec, RunConfig(**kw)); ref.run(struct)
dist = Session(spec, RunConfig(mesh=MeshConfig(mesh, shuffle_cap=512), **kw))
dist.run(struct)

mirror = nbrs.copy()
d = graph_delta(mirror, 32)            # big delta: blows past P_delta
r1 = ref.update(d); r2 = dist.update(d)
assert r1.mode == "iterMR-fallback", r1.mode
assert r2.mode == "distributed-warm", r2.mode
np.testing.assert_array_equal(ref.result["r"], dist.result["r"])
# the warm converge re-seeded the per-shard slices (the §5.2 recovery):
# the next update starts fine again, and whatever path the engine then
# picks must correspond across layouts, bit for bit
assert dist._driver.mrbg_on and dist._driver.stores
d = graph_delta(mirror, 2)
r1 = ref.update(d); r2 = dist.update(d)
mode_map = {"i2": "distributed-i2", "iterMR-fallback": "distributed-warm"}
assert r2.mode == mode_map[r1.mode], (r1.mode, r2.mode)
np.testing.assert_array_equal(ref.result["r"], dist.result["r"])
print("OK")
""")


# ---------------------------------------------------------------------------
# retrace discipline: the delta-exchange ladder compiles once per bucket
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_delta_exchange_zero_steady_retrace(backend):
    """Same bar as tests/test_stream_retrace.py: once a delta bucket is
    warm, refreshes of any size inside it trace nothing new."""
    _run(WC_PRELUDE + f"""
from repro.kernels import jitcache
dist = Session(spec, RunConfig(mesh=MeshConfig(mesh),
                               backend="{backend}", value_bytes=4))
dist.run(data)
mirror = docs.copy()
for pairs in (4, 12, 24):              # warm the row/edge buckets
    dist.update(doc_delta(mirror, pairs))
gen0 = jitcache.generation()
for pairs in (3, 10, 20):              # same buckets, different sizes
    dist.update(doc_delta(mirror, pairs))
assert jitcache.generation() == gen0, (
    f"retraced within a warm bucket: {{jitcache.trace_counts()}}")
np.testing.assert_array_equal(dist.result["c"], wc.oracle(mirror, VOCAB))
print("OK")
""")


def test_meshed_stream_session_prewarm():
    """A StreamSession over a meshed Session: prewarm covers the
    delta-exchange ladder, so the first real batch traces nothing."""
    _run(WC_PRELUDE + """
from repro.kernels import jitcache
from repro.api import StreamConfig
from repro.stream import StreamSession
ss = StreamSession(spec, data,
                   config=RunConfig(mesh=MeshConfig(mesh), backend="xla",
                                    value_bytes=4),
                   stream=StreamConfig(max_batch_delay=0.0, crossover=2.0,
                                       max_batch_records=64, prewarm=True))
ss.start(background=False)
mirror = docs.copy()
gen0 = jitcache.generation()
d = doc_delta(mirror, 32)              # 64 rows: the full bucket
ss.submit(np.asarray(d.record_ids), {"w": np.asarray(d.values["w"])},
          np.asarray(d.sign))
assert ss.step()
assert jitcache.generation() == gen0, (
    f"first real batch retraced despite prewarm: "
    f"{jitcache.trace_counts()}")
assert ss.metrics.retrace_batches == 0
np.testing.assert_array_equal(ss.result["c"], wc.oracle(mirror, VOCAB))
print("OK")
""")


# ---------------------------------------------------------------------------
# failure atomicity + capacity regrow
# ---------------------------------------------------------------------------

def test_update_failure_rolls_back():
    """A refresh that dies mid-flight (here: injected into the shard merge
    and into the warm converge) must leave the session at its pre-update
    state, and a retry must succeed."""
    _run(PR_PRELUDE + """
import repro.core.distributed as dist_mod
kw = dict(backend="xla", max_iters=60, tol=1e-7,
          cpc_threshold=5e-4, pdelta_threshold=1.0)
dist = Session(spec, RunConfig(mesh=MeshConfig(mesh, shuffle_cap=512), **kw))
dist.run(struct)
before = dist.result["r"].copy()

# fine path: die after some shards already merged/patched
mirror = nbrs.copy()
d = graph_delta(mirror, 4)
orig_merge = dist_mod.merge_shard_delta
calls = []
def bomb(*a, **k):
    if len(calls) >= 2:
        raise RuntimeError("injected merge failure")
    calls.append(1)
    return orig_merge(*a, **k)
dist_mod.merge_shard_delta = bomb
try:
    dist.update(d)
    raise SystemExit("expected injected failure")
except RuntimeError:
    pass
finally:
    dist_mod.merge_shard_delta = orig_merge
np.testing.assert_array_equal(dist.result["r"], before)

# warm path: converge itself dies
warm = Session(spec, RunConfig(
    mesh=MeshConfig(mesh, shuffle_cap=512, refresh="warm"), **kw))
warm.run(struct)
wbefore = warm.result["r"].copy()
orig_run = dist_mod.run_distributed
def boom(*a, **k):
    raise RuntimeError("shuffle capacity overflow: injected")
dist_mod.run_distributed = boom
try:
    warm.update(d)
    raise SystemExit("expected injected overflow")
except RuntimeError:
    pass
finally:
    dist_mod.run_distributed = orig_run
np.testing.assert_array_equal(warm.result["r"], wbefore)
rep = warm.update(d)                   # retry: same delta, now succeeds
assert rep.mode == "distributed-warm", rep.mode
print("OK")
""")


def test_converge_auto_regrow_reported():
    """An undersized MeshConfig.shuffle_cap self-heals up the bucket
    ladder and reports it, instead of raising."""
    _run(PR_PRELUDE + """
dist = Session(spec, RunConfig(mesh=MeshConfig(mesh, shuffle_cap=2),
                               backend="xla", max_iters=60, tol=1e-7))
rep = dist.run(struct)
assert rep.shuffle.regrows >= 1, rep.shuffle.regrows
assert rep.shuffle.shuffle_cap > 2
ref = Session(spec, RunConfig(backend="xla", max_iters=60, tol=1e-7))
ref.run(struct)
np.testing.assert_array_equal(ref.result["r"], dist.result["r"])
print("OK")
""")


# ---------------------------------------------------------------------------
# MeshConfig surface (no devices needed)
# ---------------------------------------------------------------------------

class _FakeMesh:
    shape = {"pod": 2, "data": 4}


def test_meshconfig_validation():
    from repro.api import MeshConfig, RunConfig

    mc = MeshConfig(_FakeMesh(), axis="data", pod_axis="pod")
    assert mc.n_parts == 8
    with pytest.raises(ValueError, match="axis"):
        MeshConfig(_FakeMesh(), axis="model")
    with pytest.raises(ValueError, match="pod axis"):
        MeshConfig(_FakeMesh(), pod_axis="rack")
    with pytest.raises(ValueError, match="shuffle_cap"):
        MeshConfig(_FakeMesh(), axis="data", shuffle_cap=0)
    with pytest.raises(ValueError, match="refresh"):
        MeshConfig(_FakeMesh(), axis="data", refresh="lukewarm")
    with pytest.raises(ValueError, match="mesh"):
        MeshConfig(object())


def test_flat_mesh_kwargs_removed():
    # the PR-7 deprecation window is over: RunConfig only takes a
    # MeshConfig, and the flat knobs are gone entirely
    from repro.api import MeshConfig, RunConfig

    with pytest.raises(TypeError, match="MeshConfig"):
        RunConfig(mesh=_FakeMesh())
    for bad in ({"mesh_axis": "data"}, {"pod_axis": "pod"},
                {"shuffle_cap": 128}, {"partition_cap": 64}):
        with pytest.raises(TypeError):
            RunConfig(**bad)

    mc = MeshConfig(_FakeMesh(), axis="data", pod_axis="pod",
                    shuffle_cap=128, partition_cap=64)
    cfg = RunConfig(mesh=mc)
    assert cfg.mesh is mc
    assert not hasattr(cfg, "shuffle_cap") and not hasattr(cfg, "mesh_axis")
    cfg2 = cfg.replace(tol=1e-5)
    assert cfg2.mesh is mc

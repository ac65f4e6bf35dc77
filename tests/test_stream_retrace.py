"""Latency-tail regression tests: shape bucketing must make the refresh
path trace once per delta bucket, not once per distinct row count.

Trace counting rides :mod:`repro.kernels.jitcache`: every jitted kernel on
the refresh path bumps a counter from inside its Python body, which only
executes on a jit-cache miss — so ``jitcache.generation()`` staying flat
across a batch is an exact "no retrace" witness.

The workload is sized so every shape knob lands in one bucket per stage:
vocab <= 64 keys (key bucket 64 always) and 4 words per doc (a power of
two, so delta-row buckets and edge-count buckets stay aligned across
varying batch sizes within a row bucket).
"""
import numpy as np
import pytest

from repro.api import RunConfig, StreamConfig
from repro.apps import wordcount as wc
from repro.kernels import jitcache
from repro.stream import RefreshScheduler, StreamSession

BACKENDS = ("xla", "pallas")
VOCAB = 32
L = 4                       # words per doc: power of two keeps buckets aligned


def _make(backend, n_docs=32, seed=0, **stream_kw):
    rng = np.random.default_rng(seed)
    docs = rng.integers(0, VOCAB, (n_docs, L)).astype(np.int32)
    spec, data = wc.make_job(docs, VOCAB)
    kw = dict(max_batch_delay=0.0, crossover=2.0)   # always update
    kw.update(stream_kw)
    ss = StreamSession(spec, data,
                       config=RunConfig(backend=backend, value_bytes=4),
                       stream=StreamConfig(**kw))
    return ss, docs, rng


def _push_pairs(ss, mirror, rng, n_pairs):
    """One micro-batch updating ``n_pairs`` distinct records ('-' old,
    '+' new) — 2 * n_pairs delta rows, no in-batch cancellation."""
    rows = rng.choice(len(mirror), size=n_pairs, replace=False)
    new = rng.integers(0, VOCAB, (n_pairs, L)).astype(np.int32)
    rid = np.repeat(rows.astype(np.int32), 2)
    buf = np.empty((2 * n_pairs, L), np.int32)
    buf[0::2] = mirror[rows]
    buf[1::2] = new
    mirror[rows] = new
    ss.submit(rid, {"w": buf}, np.tile(np.int8([-1, 1]), n_pairs))
    assert ss.step()


@pytest.mark.parametrize("backend", BACKENDS)
def test_no_retrace_within_bucket(backend):
    """Delta sizes that vary *within* one row bucket (and, because L is a
    power of two, one edge bucket) must not trace anything new once the
    bucket is warm."""
    ss, docs, rng = _make(backend)
    ss.start(background=False)
    mirror = docs.copy()

    # warm one batch per (row bucket, edge bucket) combination:
    # 4/12/24 pairs -> 8/24/48 rows (row bucket 64) -> 32/96/192 valid
    # edges (edge buckets 64/128/256)
    for pairs in (4, 12, 24):
        _push_pairs(ss, mirror, rng, pairs)

    gen0 = jitcache.generation()
    # probe sizes land in the same buckets: 6/20/40 rows -> 24/80/160
    # edges -> buckets 64/128/256
    for pairs in (3, 10, 20):
        _push_pairs(ss, mirror, rng, pairs)
    assert jitcache.generation() == gen0, (
        f"retraced within a warm bucket: {jitcache.trace_counts()}")
    assert ss.metrics.retrace_batches <= 3   # only the warm-up batches

    np.testing.assert_array_equal(ss.result["c"], wc.oracle(mirror, VOCAB))


@pytest.mark.parametrize("backend", BACKENDS)
def test_prewarm_compiles_the_ladder(backend):
    """With ``prewarm=True`` the bucket ladder is compiled on start();
    the first real full-bucket micro-batch then traces nothing."""
    ss, docs, rng = _make(backend, max_batch_records=64, prewarm=True)
    ss.start(background=False)
    mirror = docs.copy()

    gen0 = jitcache.generation()
    _push_pairs(ss, mirror, rng, 32)         # 64 rows: the full bucket
    assert jitcache.generation() == gen0, (
        f"first real batch retraced despite prewarm: "
        f"{jitcache.trace_counts()}")
    assert ss.metrics.retrace_batches == 0
    np.testing.assert_array_equal(ss.result["c"], wc.oracle(mirror, VOCAB))


def test_prewarm_is_a_noop_on_the_result():
    """The warm-up deltas ('-' then '+' of current values) must not change
    the job's output or the mirror."""
    ss, docs, _ = _make("xla", max_batch_records=64, prewarm=True)
    ss.start(background=False)
    np.testing.assert_array_equal(ss.result["c"], wc.oracle(docs, VOCAB))
    np.testing.assert_array_equal(
        np.asarray(ss.mirror_kv().values["w"]), docs)


def test_retraced_batches_marked_in_metrics():
    """A batch that lands in a cold bucket is flagged ``retraced`` (and its
    wall-clock excluded from the scheduler's cost model)."""
    # jit caches are process-global: 11 words per doc gives this test value
    # shapes no other test in the suite (or conftest import) has compiled yet
    rng = np.random.default_rng(21)
    docs = rng.integers(0, VOCAB, (32, 11)).astype(np.int32)
    spec, data = wc.make_job(docs, VOCAB)
    ss = StreamSession(spec, data,
                       config=RunConfig(backend="xla", value_bytes=4),
                       stream=StreamConfig(max_batch_delay=0.0,
                                           crossover=2.0))
    ss.start(background=False)
    mirror = docs.copy()

    def push(n_pairs):
        rows = rng.choice(len(mirror), size=n_pairs, replace=False)
        new = rng.integers(0, VOCAB, (n_pairs, 11)).astype(np.int32)
        rid = np.repeat(rows.astype(np.int32), 2)
        buf = np.empty((2 * n_pairs, 11), np.int32)
        buf[0::2] = mirror[rows]
        buf[1::2] = new
        mirror[rows] = new
        ss.submit(rid, {"w": buf}, np.tile(np.int8([-1, 1]), n_pairs))
        assert ss.step()

    push(4)                                  # cold bucket: traces
    assert ss.metrics.retrace_batches == 1
    assert ss.scheduler.compile_skips == 1
    push(4)                                  # warm now
    assert ss.metrics.retrace_batches == 1
    assert ss.scheduler.compile_skips == 1


def test_persistent_cache_dir_wired(tmp_path, monkeypatch):
    """RunConfig(compilation_cache_dir=...) must flip JAX's persistent
    compilation cache on and populate the directory with executables."""
    import jax

    monkeypatch.delenv(jitcache.CACHE_ENV, raising=False)
    cache = tmp_path / "xc"
    rng = np.random.default_rng(5)
    docs = rng.integers(0, VOCAB, (16, L)).astype(np.int32)
    spec, data = wc.make_job(docs, VOCAB)
    ss = StreamSession(spec, data,
                       config=RunConfig(backend="xla", value_bytes=4,
                                        compilation_cache_dir=str(cache)),
                       stream=StreamConfig(max_batch_delay=0.0,
                                           crossover=2.0))
    ss.start(background=False)
    mirror = docs.copy()
    _push_pairs(ss, mirror, rng, 4)
    assert jax.config.jax_compilation_cache_dir == str(cache)
    assert jitcache.persistent_cache_dir() == str(cache)
    assert any(cache.iterdir()), "no executables written to the cache dir"
    np.testing.assert_array_equal(ss.result["c"], wc.oracle(mirror, VOCAB))


def test_persistent_cache_env_wins(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache and
    a caller's directory is ignored: no other directory is configured."""
    import jax

    monkeypatch.setattr(jitcache, "_cache_dir", None)
    monkeypatch.setenv(jitcache.CACHE_ENV, str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    other = tmp_path / "other"
    assert jitcache.enable_persistent_cache(other) == str(tmp_path / "env")
    assert jitcache.persistent_cache_dir() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not other.exists()

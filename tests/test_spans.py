"""Host spans and transfer counters of the refresh path (repro.core.spans),
and the named scopes of the merge's device stages.

A stream wordcount on the MRBG path records, per epoch, one span tree
under ``repro.stream.step`` with one epoch id, and counts the bytes of
every host<->device copy; the counts follow from the bucket shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RunConfig, StreamConfig
from repro.apps import wordcount as wc
from repro.core import spans
from repro.core.incremental import _merge_reduce
from repro.core.kvstore import Edges, next_bucket, sum_reducer
from repro.kernels import jitcache
from repro.stream import StreamSession

VOCAB, L, DOCS, PAIRS = 16, 4, 64, 8

# every span of one micro-batch on the MRBG path, in the order they open
STEP = ["repro.stream.step", "repro.stream.prepare", "repro.session.update",
        "repro.incremental.delta_map", "repro.incremental.feed",
        "repro.mrbg_store.query", "repro.incremental.feed",
        "repro.incremental.merge", "repro.mrbg_store.append",
        "repro.incremental.patch", "repro.mrbg_store.append"]
PARENT = {"repro.stream.step": None,
          "repro.stream.prepare": "repro.stream.step",
          "repro.session.update": "repro.stream.step"}


def _session(backend):
    rng = np.random.default_rng(7)
    docs = rng.integers(0, VOCAB, (DOCS, L)).astype(np.int32)
    spec, data = wc.make_job(docs, VOCAB)
    ss = StreamSession(spec, data,
                       config=RunConfig(backend=backend, value_bytes=4,
                                        onestep_path="mrbg"),
                       stream=StreamConfig(max_batch_delay=0.0,
                                           crossover=2.0))
    ss.start(background=False)
    return ss, docs, rng


def _batch(ss, docs, rng):
    """Rewrite PAIRS distinct documents in one micro-batch ('-' old, '+'
    new); returns the old and new rows."""
    rows = rng.choice(DOCS, size=PAIRS, replace=False)
    new = rng.integers(0, VOCAB, (PAIRS, L)).astype(np.int32)
    buf = np.empty((2 * PAIRS, L), np.int32)
    buf[0::2], buf[1::2] = docs[rows], new
    docs[rows] = new
    ss.submit(np.repeat(rows.astype(np.int32), 2), {"w": buf},
              np.tile(np.int8([-1, 1]), PAIRS))
    return buf


def _live_edges(store):
    """Live MRBG records per key."""
    return np.where(store.idx_batch >= 0, store.idx_len, 0)


def _expected_bytes(live, buf):
    """(h2d, d2h) bytes of one micro-batch of ``buf`` rows, none of them
    cancelled, against a store holding ``live`` records per key, from the
    bucket shapes of each copy."""
    n = buf.shape[0]
    cap = next_bucket(n, 64)                    # the coalescer's bucket
    edges = int((buf >= 0).sum())               # valid delta edges
    affected = np.unique(buf[buf >= 0])
    preserved = int(live[affected].sum())
    merge_cap = next_bucket(preserved + edges, 64)
    key_cap = next_bucket(affected.size, 64)
    edge = 4 + 4 + 4 + 1 + 1                    # k2, mk, c, valid, sign
    delta_row = 4 + 4 * L + 1                   # record id, words, sign
    h2d = (cap * (4 + 1 + 1)                    # coalescer: ids, signs, valid
           + n * delta_row                      # the coalesced delta
           + merge_cap * edge + key_cap * 4)    # the merge's input, keys
    d2h = (cap * (4 + 1 + 1 + 4 + 4)            # perm, keep, firsts, net, cnt
           + n * (delta_row + 1 + 4)            # the delta for the mirror
           + 4 + next_bucket(edges, 64) * edge  # delta edges: count, prefix
           + merge_cap * edge                   # the merged edges
           + key_cap * (4 + 4))                 # counts, values
    return h2d, d2h


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_stream_refresh_spans_and_transfer_bytes(backend):
    ss, docs, rng = _session(backend)
    _batch(ss, docs, rng)
    assert ss.step()                            # compiles every bucket
    traced = jitcache.trace_counts()
    for _ in range(3):
        live = _live_edges(ss.session.store)
        buf = _batch(ss, docs, rng)
        assert ss.step()
        rep = ss.session.history[-1]
        assert rep.mode == "incremental"
        assert [s.name for s in rep.spans] == STEP
        assert {s.epoch for s in rep.spans} == {rep.epoch}
        for s in rep.spans:
            assert s.parent == PARENT.get(s.name, "repro.session.update")
            assert s.end >= s.start
        root = rep.spans[0]
        assert all(root.start <= s.start <= s.end <= root.end
                   for s in rep.spans[1:])
        h2d, d2h = _expected_bytes(live, buf)
        assert rep.counters == {"h2d_bytes": h2d, "d2h_bytes": d2h}
    # spans and counters add no trace: the warm buckets stay warm
    assert jitcache.trace_counts() == traced
    np.testing.assert_array_equal(ss.result["c"], wc.oracle(docs, VOCAB))


def test_span_nesting_epochs_and_take():
    spans.take()
    with spans.span("outer", epoch=5):
        with spans.span("inner"):
            spans.count("bytes", 3)
        spans.count("bytes", 4)
        got, counters = spans.take()
        with spans.span("after"):
            pass
    assert [s.name for s in got] == ["outer", "inner"]
    assert [s.parent for s in got] == [None, "outer"]
    assert [s.epoch for s in got] == [5, 5]
    assert counters == {"bytes": 7}
    # a span open at take() is handed over and closed later
    assert got[0].end is not None and got[0].end >= got[1].end
    rest, counters = spans.take()
    assert [(s.name, s.parent, s.epoch) for s in rest] == [
        ("after", "outer", 5)]
    assert counters == {}
    assert spans.take() == ([], {})


def test_transfers_count_only_across_the_boundary():
    spans.take()
    host = np.arange(10, dtype=np.int32)
    dev = spans.to_device(host)                 # host -> device: counted
    spans.to_device(dev)                        # already there: not
    spans.to_device([1.0, 2.0], jnp.float32)    # counted as it lands
    back = spans.to_host(dev)                   # device -> host: counted
    spans.to_host(back)                         # already there: not
    assert spans.take()[1] == {"h2d_bytes": 40 + 8, "d2h_bytes": 40}


def test_merge_stages_are_named_scopes():
    """The compiled merge carries each stage of ops.shuffle_reduce as a
    named scope in its operations' op_name metadata."""
    n, keys = 1 << 10, 64
    lane = lambda dt: jax.ShapeDtypeStruct((n,), dt)
    combined = Edges(lane(jnp.int32), lane(jnp.int32),
                     {"c": lane(jnp.float32)}, lane(jnp.bool_),
                     lane(jnp.int8))
    text = _merge_reduce.lower(
        sum_reducer(), keys, "xla", combined,
        jax.ShapeDtypeStruct((keys,), jnp.int32)).compile().as_text()
    for stage in ("sort", "last_writer", "route", "reduce"):
        assert f"/shuffle_reduce/{stage}/" in text, stage

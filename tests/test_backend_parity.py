"""Backend parity: the Pallas kernels and the XLA fallback must agree
bit-for-bit through the dispatcher (``repro.kernels.ops``).

Both backends implement a total order for the shuffle sort (k2, mk, row
index), so the permutation — not just the sorted keys — must match exactly.
Segment reductions are compared on integer-valued data (ints, and floats
holding small integers) where the sum is exact regardless of accumulation
order, so equality is bitwise there too.
"""
import os

import numpy as np
import jax.numpy as jnp
import pytest

from _hyp import given, settings, st  # hypothesis, or seeded fallback

from repro.core.incremental import _combine_edges, _merge_reduce
from repro.core.kvstore import (
    INVALID_KEY, make_edges, max_reducer, mean_reducer, min_reducer,
    segment_reduce, sort_edges, sum_reducer,
)
from repro.kernels import ops

REDUCERS = {
    "sum": sum_reducer(),
    "min": min_reducer(),
    "max": max_reducer(),
    "mean": mean_reducer(),
}


def _both(fn):
    return fn("xla"), fn("pallas")


# ---------------------------------------------------------------------------
# sort_pairs
# ---------------------------------------------------------------------------

@given(st.integers(1, 300), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_sort_pairs_permutation_parity(n, seed):
    """Non-power-of-two lengths, duplicate keys, ties broken identically."""
    rng = np.random.default_rng(seed % 2**31)
    k2 = jnp.asarray(rng.integers(0, max(n // 4, 2), n), jnp.int32)
    mk = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
    payload = {"a": jnp.asarray(rng.integers(-100, 100, n), jnp.int32),
               "b": jnp.asarray(rng.integers(0, 9, (n, 2)), jnp.int32)}
    rx, rp = _both(lambda bk: ops.sort_pairs(k2, mk, payload, backend=bk))
    np.testing.assert_array_equal(np.asarray(rx.perm), np.asarray(rp.perm))
    np.testing.assert_array_equal(np.asarray(rx.k2), np.asarray(rp.k2))
    np.testing.assert_array_equal(np.asarray(rx.mk), np.asarray(rp.mk))
    for name in payload:
        np.testing.assert_array_equal(np.asarray(rx.payload[name]),
                                      np.asarray(rp.payload[name]))


@given(st.integers(1, 200), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_sort_edges_parity_with_invalid_rows(n, seed):
    rng = np.random.default_rng(seed % 2**31)
    e = make_edges(rng.integers(0, 8, n), rng.integers(0, 50, n),
                   {"v": jnp.asarray(rng.integers(-4, 5, (n, 3)),
                                     jnp.float32)},
                   valid=rng.random(n) < 0.7,
                   sign=np.where(rng.random(n) < 0.2, -1, 1).astype(np.int8))
    sx, sp = _both(lambda bk: sort_edges(e, backend=bk))
    for name in ("k2", "mk", "valid", "sign"):
        np.testing.assert_array_equal(np.asarray(getattr(sx, name)),
                                      np.asarray(getattr(sp, name)))
    np.testing.assert_array_equal(np.asarray(sx.v2["v"]),
                                  np.asarray(sp.v2["v"]))
    # invalid rows masked to INVALID_KEY and pushed to the tail
    k2 = np.asarray(sp.k2)
    valid = np.asarray(sp.valid)
    assert (k2[~valid] == int(INVALID_KEY)).all()


@pytest.mark.parametrize("n", [1023, 1025, 4095, 4097, 9000])
def test_sort_pairs_parity_tile_straddling(n):
    """Sizes on both sides of the one-vreg minimum and of SORT_TILE (the
    multi-tile network), with heavy ties on both keys."""
    rng = np.random.default_rng(n)
    k2 = jnp.asarray(rng.integers(0, max(n // 64, 2), n), jnp.int32)
    mk = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
    rx, rp = _both(lambda bk: ops.sort_pairs(k2, mk, None, backend=bk))
    for name in ("perm", "k2", "mk"):
        np.testing.assert_array_equal(np.asarray(getattr(rx, name)),
                                      np.asarray(getattr(rp, name)))


@pytest.mark.parametrize("n,nflags,num_keys", [
    (1, 1, 2), (300, 2, 2), (1025, 3, 2), (4097, 2, 2), (300, 3, 1)])
def test_sort_pairs_flags_parity(n, nflags, num_keys):
    """Flags co-sorted by the sort itself (the index lane's low bits on
    pallas, sort operands on xla): bitwise equal on both backends, equal
    to ``flag[perm]``, and the rest of the sort as without them."""
    rng = np.random.default_rng(n + nflags)
    k2 = jnp.asarray(rng.integers(0, max(n // 16, 2), n), jnp.int32)
    mk = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
    flags = tuple(jnp.asarray(rng.random(n) < 0.5) for _ in range(nflags))
    payload = {"v": jnp.asarray(rng.integers(-9, 9, (n, 2)), jnp.float32)}
    plain = ops.sort_pairs(k2, mk, payload, num_keys=num_keys,
                           backend="xla")
    rx, rp = _both(lambda bk: ops.sort_pairs(
        k2, mk, payload, num_keys=num_keys, flags=flags, backend=bk))
    perm = np.asarray(plain.perm)
    for r in (rx, rp):
        for name in ("k2", "mk", "perm"):
            np.testing.assert_array_equal(np.asarray(getattr(r, name)),
                                          np.asarray(getattr(plain, name)),
                                          err_msg=name)
        np.testing.assert_array_equal(np.asarray(r.payload["v"]),
                                      np.asarray(plain.payload["v"]))
        assert len(r.flags) == nflags
        for got, f in zip(r.flags, flags):
            assert got.dtype == jnp.bool_
            np.testing.assert_array_equal(np.asarray(got), np.asarray(f)[perm])


def test_sort_pairs_too_many_flags_refused():
    k2 = jnp.zeros(4, jnp.int32)
    flags = (jnp.ones(4, bool),) * (ops.MAX_FLAGS + 1)
    for bk in ("xla", "pallas"):
        with pytest.raises(ValueError):
            ops.sort_pairs(k2, k2, flags=flags, backend=bk)


def test_sort_pairs_single_key_stable():
    rng = np.random.default_rng(0)
    n = 129                                     # non-power-of-two
    k2 = jnp.asarray(rng.integers(0, 4, n), jnp.int32)
    rx, rp = _both(lambda bk: ops.sort_pairs(k2, None, num_keys=1,
                                             backend=bk))
    np.testing.assert_array_equal(np.asarray(rx.perm), np.asarray(rp.perm))
    # stability: equal keys keep input order
    perm = np.asarray(rp.perm)
    k2n = np.asarray(k2)
    for key in range(4):
        idx = perm[k2n[perm] == key]
        assert (np.diff(idx) > 0).all()


# ---------------------------------------------------------------------------
# segment_reduce: all four Reducer kinds, pytree values, >1-D leaves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["sum", "min", "max", "mean"])
@given(st.integers(1, 257), st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
def test_segment_reduce_parity(kind, n, seed):
    rng = np.random.default_rng(seed % 2**31)
    k = int(rng.integers(1, 40))
    seg = jnp.asarray(rng.integers(0, k + 2, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    values = {
        # integer-valued float32: order-independent exact sums
        "f": jnp.asarray(rng.integers(-8, 9, n).astype(np.float32)),
        "m": jnp.asarray(rng.integers(-8, 9, (n, 3)).astype(np.float32)),
        "i": jnp.asarray(rng.integers(-100, 100, n), jnp.int32),
        # int32 near both limits: sums wrap modulo 2**32 on both backends
        "w": jnp.asarray(np.where(rng.random(n) < 0.5, -1, 1)
                         * rng.integers(2**31 - 300, 2**31 - 1, n),
                         jnp.int32),
        # 3-D leaf: the pallas path flattens trailing dims
        "t": jnp.asarray(rng.integers(0, 5, (n, 2, 2)).astype(np.float32)),
    }
    (ax, cx), (ap, cp) = _both(
        lambda bk: segment_reduce(REDUCERS[kind], seg, values, valid, k,
                                  backend=bk))
    np.testing.assert_array_equal(np.asarray(cx), np.asarray(cp))
    for name in values:
        np.testing.assert_array_equal(
            np.asarray(ax[name]), np.asarray(ap[name]),
            err_msg=f"kind={kind} leaf={name}")


def test_segment_reduce_empty_groups_identity_parity():
    """Groups with no valid rows must agree (sum: 0, min/max: identity)."""
    seg = jnp.asarray([0, 0, 5], jnp.int32)
    valid = jnp.asarray([True, True, False])
    vals = {"v": jnp.asarray([1.0, 2.0, 7.0], jnp.float32)}
    for kind in ("sum", "min", "max", "mean"):
        (ax, cx), (ap, cp) = _both(
            lambda bk: segment_reduce(REDUCERS[kind], seg, vals, valid, 8,
                                      backend=bk))
        np.testing.assert_array_equal(np.asarray(ax["v"]),
                                      np.asarray(ap["v"]))
        np.testing.assert_array_equal(np.asarray(cx), np.asarray(cp))
        assert int(np.asarray(cp)[5]) == 0


# ---------------------------------------------------------------------------
# tombstone merge (incremental._merge_reduce): last writer wins on both
# ---------------------------------------------------------------------------

@given(st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
def test_merge_reduce_tombstone_parity(seed):
    rng = np.random.default_rng(seed % 2**31)
    key_cap = 64
    npres, ndelta = int(rng.integers(1, 60)), int(rng.integers(1, 60))
    # preserved edges: all +1; delta edges: mix of tombstones and inserts,
    # some hitting the same (k2, mk) as preserved rows (updates)
    pk2 = rng.integers(0, 8, npres).astype(np.int32)
    pmk = rng.integers(0, 20, npres).astype(np.int32)
    pv = {"v": rng.integers(-8, 9, npres).astype(np.float32)}
    dk2 = rng.integers(0, 8, ndelta).astype(np.int32)
    dmk = rng.integers(0, 20, ndelta).astype(np.int32)
    dv = {"v": rng.integers(-8, 9, ndelta).astype(np.float32)}
    dsign = np.where(rng.random(ndelta) < 0.4, -1, 1).astype(np.int8)

    affected = np.unique(np.concatenate([pk2, dk2]))
    keys_pad = np.full(key_cap, np.int32(2**31 - 1), np.int32)
    keys_pad[:affected.size] = affected

    def run(bk):
        # combined buffer is donated, so build it fresh per backend
        combined = _combine_edges(pk2, pmk, pv, dk2, dmk, dv, dsign)
        return _merge_reduce(sum_reducer(), key_cap, bk, combined,
                             jnp.asarray(keys_pad))

    (mx, vx, cx), (mp, vp, cp) = _both(run)
    np.testing.assert_array_equal(np.asarray(cx), np.asarray(cp))
    np.testing.assert_array_equal(np.asarray(vx["v"]), np.asarray(vp["v"]))
    # the merged (live) edge sets agree
    lx = {(int(a), int(b)) for a, b, ok in
          zip(np.asarray(mx.k2), np.asarray(mx.mk), np.asarray(mx.valid))
          if ok}
    lp = {(int(a), int(b)) for a, b, ok in
          zip(np.asarray(mp.k2), np.asarray(mp.mk), np.asarray(mp.valid))
          if ok}
    assert lx == lp
    # last-writer-wins: a (k2, mk) whose final delta row is a tombstone
    # must not be live
    final_sign = {}
    for a, b in zip(pk2, pmk):
        final_sign[(int(a), int(b))] = 1
    for a, b, s in zip(dk2, dmk, dsign):
        final_sign[(int(a), int(b))] = int(s)
    want_live = {k for k, s in final_sign.items() if s > 0}
    assert lp == want_live


# ---------------------------------------------------------------------------
# backend selection plumbing
# ---------------------------------------------------------------------------

def test_backend_selection_precedence(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert ops.resolve_backend("xla") == "xla"
    assert ops.resolve_backend("pallas") == "pallas"
    # auto resolves by platform (cpu container => xla)
    import jax
    want_auto = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert ops.resolve_backend(None) == want_auto
    # env var
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    assert ops.resolve_backend(None) == "pallas"
    # config beats env; context manager restores
    with ops.use_backend("xla"):
        assert ops.resolve_backend(None) == "xla"
        # per-call beats config
        assert ops.resolve_backend("pallas") == "pallas"
    assert ops.resolve_backend(None) == "pallas"
    monkeypatch.setenv("REPRO_BACKEND", "bogus")
    with pytest.raises(ValueError):
        ops.resolve_backend(None)


def test_invalid_backend_rejected():
    with pytest.raises(ValueError):
        ops.set_backend("cuda")
    with pytest.raises(ValueError):
        ops.resolve_backend("bogus")

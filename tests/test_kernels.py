"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret mode on CPU; identical code lowers natively on TPU)."""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ref import sort_lex_ref
from repro.kernels.segment_reduce import (
    INT_ROWS_MAX, segment_minmax_mxu, segment_minmax_ref, segment_reduce_mxu,
    segment_reduce_ref, segment_sum_counts_mxu, segment_sum_mxu,
)
from repro.kernels.flash_attention import flash_attention, mha_ref
from repro.kernels.sort_u32 import (
    MIN_TILE, sort_kv32, sort_kv32_ref, sort_lex_pallas,
)
from repro.kernels.spmv_ell import spmv_ell, spmv_ell_ref


class TestSegmentReduce:
    @pytest.mark.parametrize("n,d,k", [(256, 8, 64), (1000, 16, 300),
                                       (64, 128, 17), (512, 1, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, n, d, k, dtype):
        rng = np.random.default_rng(n + d + k)
        seg = jnp.asarray(rng.integers(0, k + 3, n), jnp.int32)
        vals = jnp.asarray(rng.normal(0, 1, (n, d)), dtype)
        got = segment_reduce_mxu(seg, vals, k, rows=128, kblk=128)
        want = segment_reduce_ref(seg, vals, k)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol)


class TestSegmentSumExactInt:
    """int32 sums via byte limbs on the MXU: bitwise equal to
    ``jax.ops.segment_sum`` (wrapping modulo 2**32)."""

    @pytest.mark.parametrize("n,d,k", [(7, 1, 3), (1023, 2, 40),
                                       (1024, 3, 300), (1025, 1, 5),
                                       (2049, 2, 700)])
    @pytest.mark.parametrize("counts", [False, True])
    def test_near_int32_limits(self, n, d, k, counts):
        rng = np.random.default_rng(n * 7 + d)
        seg = jnp.asarray(rng.integers(0, k + 2, n), jnp.int32)
        mag = rng.integers(2**31 - 1000, 2**31, (n, d))
        vals = np.where(rng.random((n, d)) < 0.5, -mag, mag - 1)
        vals = jnp.asarray(vals.astype(np.int32))
        want = jax.ops.segment_sum(vals, seg, num_segments=k + 2)[:k]
        if counts:
            got, cnt = segment_sum_counts_mxu(seg, vals, k,
                                              out_dtype=jnp.int32)
            np.testing.assert_array_equal(
                np.asarray(cnt),
                np.bincount(np.asarray(seg), minlength=k + 2)[:k])
        else:
            got = segment_sum_mxu(seg, vals, k, out_dtype=jnp.int32)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_segment_past_2_24_rows(self):
        """Counts and sums stay exact where float32 would round."""
        n = (1 << 24) + 3
        seg = jnp.zeros(n, jnp.int32).at[-1].set(1)
        vals = jnp.full((n, 1), 3, jnp.int32)
        acc, cnt = segment_sum_counts_mxu(seg, vals, 2, out_dtype=jnp.int32,
                                          rows=INT_ROWS_MAX)
        np.testing.assert_array_equal(np.asarray(cnt), [n - 1, 1])
        np.testing.assert_array_equal(np.asarray(acc)[:, 0],
                                      [3 * (n - 1), 3])


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,kh,s,hd", [
        (1, 2, 2, 128, 32), (2, 4, 2, 256, 32), (1, 8, 1, 128, 64)])
    @pytest.mark.parametrize("opts", [
        dict(causal=True), dict(causal=False),
        dict(causal=True, window=64), dict(causal=True, softcap=50.0)])
    def test_sweep(self, b, h, kh, s, hd, opts):
        rng = np.random.default_rng(b * 100 + h)
        q = jnp.asarray(rng.normal(0, 1, (b, h, s, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(0, 1, (b, kh, s, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(0, 1, (b, kh, s, hd)), jnp.float32)
        got = flash_attention(q, k, v, q_blk=64, kv_blk=64, **opts)
        want = mha_ref(q, k, v, **opts)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)

    def test_bf16(self):
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.normal(0, 1, (1, 2, 128, 32)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(0, 1, (1, 2, 128, 32)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(0, 1, (1, 2, 128, 32)), jnp.bfloat16)
        got = flash_attention(q, k, v, q_blk=64, kv_blk=64)
        want = mha_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=5e-2)


class TestSort:
    @pytest.mark.parametrize("n", [16, 100, 700, 1024, 4096])
    def test_sweep(self, n):
        rng = np.random.default_rng(n)
        keys = jnp.asarray(rng.integers(0, max(10, n), n), jnp.uint32)
        payload = jnp.arange(n, dtype=jnp.int32)
        gk, gp = sort_kv32(keys, payload)
        wk, _ = sort_kv32_ref(keys, payload)
        np.testing.assert_array_equal(np.asarray(gk), np.asarray(wk))
        # payload is a permutation consistent with the sorted keys
        np.testing.assert_array_equal(
            np.asarray(keys)[np.asarray(gp)], np.asarray(gk))
        assert sorted(np.asarray(gp).tolist()) == list(range(n))


class TestSortMultiTile:
    """The cross-tile bitonic merge: sizes straddling every tile boundary.

    ``tile=MIN_TILE`` (one (8, 128) vreg) is the smallest tile the native
    lowering accepts; it keeps the multi-tile machinery cheap in interpret
    mode while exercising the same code path the default SORT_TILE takes
    for inputs past one VMEM tile.
    """

    TILE = MIN_TILE

    @pytest.mark.parametrize("n", [0, 1, 63, 64, 65, 127, 128, 129,
                                   200, 256, 515, 1024, 1025, 2047, 2049,
                                   3000, 4097])
    def test_boundary_sweep(self, n):
        rng = np.random.default_rng(n + 17)
        hi = jnp.asarray(rng.integers(0, max(n // 2, 2), n), jnp.int32)
        lo = jnp.asarray(rng.integers(0, 7, n), jnp.int32)
        gh, gl, gp = sort_lex_pallas(hi, lo, tile=self.TILE)
        wh, wl, wp = sort_lex_ref(hi, lo)
        np.testing.assert_array_equal(np.asarray(gh), np.asarray(wh))
        np.testing.assert_array_equal(np.asarray(gl), np.asarray(wl))
        # stability: with the unique index lane the permutation is unique,
        # so it must match the stable oracle exactly
        np.testing.assert_array_equal(np.asarray(gp), np.asarray(wp))

    def test_all_equal_keys_stability(self):
        n = 5 * self.TILE              # non-pow2 count of tiles
        hi = jnp.zeros(n, jnp.int32)
        lo = jnp.zeros(n, jnp.int32)
        _, _, perm = sort_lex_pallas(hi, lo, tile=self.TILE)
        np.testing.assert_array_equal(np.asarray(perm), np.arange(n))

    @pytest.mark.parametrize("n", [1000, 3 * MIN_TILE + 7])
    def test_ties_keep_input_order(self, n):
        """Few distinct keys, all secondary keys equal: every run of ties
        must come out in input order, within and across tiles."""
        rng = np.random.default_rng(n)
        hi = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
        lo = jnp.full(n, 4, jnp.int32)
        gh, _, gp = sort_lex_pallas(hi, lo, tile=self.TILE)
        gp, gh = np.asarray(gp), np.asarray(gh)
        for key in range(3):
            assert (np.diff(gp[gh == key]) > 0).all()
        np.testing.assert_array_equal(gp, np.asarray(sort_lex_ref(hi, lo)[2]))

    def test_extreme_keys(self):
        """Keys at the int32 extremes, including the padding value itself:
        real rows equal to the pad key still sort ahead of the padding."""
        rng = np.random.default_rng(11)
        n = MIN_TILE + 300
        pool = np.array([-2**31, -2**31 + 1, -1, 0, 1, 2**31 - 2, 2**31 - 1],
                        np.int32)
        hi = jnp.asarray(rng.choice(pool, n))
        lo = jnp.asarray(rng.choice(pool, n))
        got = sort_lex_pallas(hi, lo, tile=self.TILE)
        for a, b in zip(got, sort_lex_ref(hi, lo)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_tile_below_one_vreg_rejected(self):
        hi = jnp.zeros(8, jnp.int32)
        with pytest.raises(ValueError):
            sort_lex_pallas(hi, hi, tile=MIN_TILE // 2)

    def test_vmem_bounded_padding(self):
        # a few tiles + 1 row must pad to the next tile multiple of the
        # network, not to the next power of two of a single giant tile
        n = 4 * self.TILE + 1
        rng = np.random.default_rng(0)
        hi = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
        lo = jnp.zeros(n, jnp.int32)
        gh, _, gp = sort_lex_pallas(hi, lo, tile=self.TILE)
        assert gh.shape == (n,)
        assert sorted(np.asarray(gp).tolist()) == list(range(n))

    def test_matches_default_tile(self):
        n = 3000
        rng = np.random.default_rng(3)
        hi = jnp.asarray(rng.integers(0, 40, n), jnp.int32)
        lo = jnp.asarray(rng.integers(0, 5, n), jnp.int32)
        small = sort_lex_pallas(hi, lo, tile=self.TILE)   # multi-tile
        big = sort_lex_pallas(hi, lo)          # single-tile path
        for a, b in zip(small, big):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tag_case(name):
    """(hi, lo, tile) of one tagged-sort case."""
    rng = np.random.default_rng(len(name))
    n, tile = {"single_tile": (700, 4096), "multi_tile": (4 * MIN_TILE,
                                                          MIN_TILE),
               "pad_rows": (2 * MIN_TILE + 37, MIN_TILE),
               "all_equal": (5 * MIN_TILE // 2, MIN_TILE),
               "ties": (3 * MIN_TILE + 7, MIN_TILE)}[name]
    hi = rng.integers(0, max(n // 8, 2), n)
    lo = rng.integers(0, 5, n)
    if name == "all_equal":
        hi, lo = np.zeros(n), np.zeros(n)
    elif name == "ties":                 # few keys, extremes, the pad key
        hi = rng.choice(np.array([-2**31, 0, 2**31 - 1]), n)
        lo = np.full(n, 2**31 - 1)
    return jnp.asarray(hi, jnp.int32), jnp.asarray(lo, jnp.int32), tile


class TestSortTag:
    """A tag under the index lane leaves the sort as it was and comes
    back as ``tag[perm]``."""

    @pytest.mark.parametrize("tag_bits", [1, 2, 3])
    @pytest.mark.parametrize("case", ["single_tile", "multi_tile",
                                      "pad_rows", "all_equal", "ties"])
    def test_tag_rides_the_index_lane(self, case, tag_bits):
        hi, lo, tile = _tag_case(case)
        rng = np.random.default_rng(tag_bits)
        tag = jnp.asarray(rng.integers(0, 1 << tag_bits, hi.shape[0]),
                          jnp.int32)
        want = sort_lex_pallas(hi, lo, tile=tile)
        *got, got_tag = sort_lex_pallas(hi, lo, tile=tile, tag=tag,
                                        tag_bits=tag_bits)
        for a, b, name in zip(got, want, ("hi", "lo", "perm")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(got_tag), np.asarray(tag)[np.asarray(want[2])])

    def test_widest_tag_at_the_limit(self):
        """2,048 padded rows leave 20 tag bits: the largest index lane
        value is exactly int32 max, and every bit comes back."""
        n, bits = 2048, 20
        rng = np.random.default_rng(20)
        hi = jnp.asarray(rng.integers(0, 50, n), jnp.int32)
        lo = jnp.zeros(n, jnp.int32)
        tag = jnp.asarray(rng.integers(0, 1 << bits, n), jnp.int32)
        _, _, perm, got = sort_lex_pallas(hi, lo, tag=tag, tag_bits=bits)
        np.testing.assert_array_equal(np.asarray(perm),
                                      np.asarray(sort_lex_ref(hi, lo)[2]))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(tag)[np.asarray(perm)])

    @pytest.mark.parametrize("n,bits", [(2**29 + 1, 2), (2049, 20)],
                             ids=["merge_flags", "one_row_past"])
    def test_length_past_the_limit_refused(self, n, bits):
        lane = jax.ShapeDtypeStruct((n,), jnp.int32)
        with pytest.raises(ValueError, match="overflow"):
            jax.eval_shape(lambda hi, lo, t: sort_lex_pallas(
                hi, lo, tag=t, tag_bits=bits), lane, lane, lane)

    def test_tag_without_bits_refused(self):
        hi = jnp.zeros(8, jnp.int32)
        with pytest.raises(ValueError):
            sort_lex_pallas(hi, hi, tag=hi)
        with pytest.raises(ValueError):
            sort_lex_pallas(hi, hi, tag_bits=2)


class TestSegmentReduceEdgeCases:
    """n=0 / num_segments=0 must return empty results, not crash."""

    def test_empty_rows(self):
        seg = jnp.zeros(0, jnp.int32)
        vals = jnp.zeros((0, 4), jnp.float32)
        out = segment_sum_mxu(seg, vals, 8)
        np.testing.assert_array_equal(np.asarray(out), np.zeros((8, 4)))
        acc, cnt = segment_sum_counts_mxu(seg, vals, 8)
        np.testing.assert_array_equal(np.asarray(cnt), np.zeros(8, np.int32))
        mn = segment_minmax_mxu("min", seg, vals, 8)
        assert np.all(np.asarray(mn) == np.inf)

    def test_zero_segments(self):
        rng = np.random.default_rng(1)
        seg = jnp.asarray(rng.integers(0, 4, 32), jnp.int32)
        vals = jnp.asarray(rng.normal(0, 1, (32, 3)), jnp.float32)
        assert segment_sum_mxu(seg, vals, 0).shape == (0, 3)
        acc, cnt = segment_sum_counts_mxu(seg, vals, 0)
        assert acc.shape == (0, 3) and cnt.shape == (0,)
        assert segment_minmax_mxu("max", seg, vals, 0).shape == (0, 3)

    def test_empty_both_backends_via_dispatcher(self):
        from repro.kernels import ops
        vals = {"v": jnp.zeros((0, 2), jnp.float32)}
        for bk in ("xla", "pallas"):
            acc, cnt = ops.segment_reduce("sum", jnp.zeros(0, jnp.int32),
                                          vals, jnp.zeros(0, bool), 4,
                                          backend=bk)
            np.testing.assert_array_equal(np.asarray(acc["v"]),
                                          np.zeros((4, 2)))
            np.testing.assert_array_equal(np.asarray(cnt),
                                          np.zeros(4, np.int32))


class TestSegmentMinMaxSublane:
    """The scatter-free sublane min/max against the jnp oracle."""

    @pytest.mark.parametrize("n,d,k", [(7, 3, 5), (256, 8, 64),
                                       (1000, 16, 300), (513, 4, 129)])
    @pytest.mark.parametrize("kind", ["min", "max"])
    def test_sweep(self, n, d, k, kind):
        rng = np.random.default_rng(n * 31 + d)
        seg = jnp.asarray(rng.integers(0, k + 2, n), jnp.int32)
        vals = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)
        got = segment_minmax_mxu(kind, seg, vals, k, rows=64, kblk=64)
        want = segment_minmax_ref(kind, seg, vals, k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("kind", ["min", "max"])
    def test_int32(self, kind):
        rng = np.random.default_rng(5)
        seg = jnp.asarray(rng.integers(0, 9, 100), jnp.int32)
        vals = jnp.asarray(rng.integers(-50, 50, (100, 2)), jnp.int32)
        got = segment_minmax_mxu(kind, seg, vals, 9)
        want = segment_minmax_ref(kind, seg, vals, 9)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_counts_ride_sum_launch(self):
        rng = np.random.default_rng(2)
        n, d, k = 300, 4, 32
        seg = jnp.asarray(rng.integers(0, k, n), jnp.int32)
        vals = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)
        acc, cnt = segment_sum_counts_mxu(seg, vals, k)
        np.testing.assert_array_equal(np.asarray(acc),
                                      np.asarray(segment_reduce_ref(seg, vals, k)))
        np.testing.assert_array_equal(
            np.asarray(cnt), np.bincount(np.asarray(seg), minlength=k)[:k])


class TestShuffleReduce:
    """ops.shuffle_reduce, pallas vs xla: bitwise on integer-valued data.

    The xla path is the reference; the pallas path must agree on every
    output (sorted lanes, permutation, live mask, accumulators, counts)
    at sizes straddling the sort tile boundary.
    """

    @staticmethod
    def _case(n, nkeys, d, seed):
        rng = np.random.default_rng(seed)
        k2 = rng.integers(0, nkeys, n).astype(np.int32)
        mk = rng.integers(0, 40, n).astype(np.int32)
        # integer-valued floats: sums are exact, parity is bitwise
        vals = rng.integers(-20, 20, (n, d)).astype(np.float32)
        valid = rng.random(n) < 0.9
        sign = np.where(rng.random(n) < 0.75, 1, -1).astype(np.int8)
        aff = np.unique(k2[valid])
        cap = 1 << max(int(np.ceil(np.log2(max(aff.size, 1)))), 3)
        keys = np.full(cap, 2**31 - 1, np.int32)
        keys[:aff.size] = aff
        return tuple(jnp.asarray(a) for a in (k2, mk, vals, valid, sign,
                                              keys))

    class _Sum:
        kind = "sum"

    @pytest.mark.parametrize("n", [5, 100, 513, 1000, 4097])
    def test_pallas_vs_xla_bitwise(self, n):
        from repro.kernels import ops
        args = self._case(n, max(n // 4, 2), 3, n)
        ref = ops.shuffle_reduce(self._Sum(), *args, backend="xla")
        got = ops.shuffle_reduce(self._Sum(), *args, backend="pallas")
        for name in ("k2", "mk", "live", "perm", "counts"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(ref, name)), err_msg=name)
        np.testing.assert_array_equal(np.asarray(got.acc),
                                      np.asarray(ref.acc))
        np.testing.assert_array_equal(np.asarray(got.values),
                                      np.asarray(ref.values))

    def test_stability_witness(self):
        """Duplicate (k2, mk) rows: the *last* writer must win through the
        multi-tile sort (the engine's tombstone semantics)."""
        from repro.kernels import ops
        n, reps = 3 * 1400, 3                   # past one SORT_TILE
        k2 = jnp.asarray(np.repeat(np.arange(n // reps, dtype=np.int32),
                                   reps))
        mk = jnp.zeros(n, jnp.int32)
        vals = jnp.asarray(np.arange(n, dtype=np.float32)[:, None])
        valid = jnp.ones(n, bool)
        sign = jnp.ones(n, np.int8)
        keys = jnp.asarray(np.arange(2048, dtype=np.int32))
        out = ops.shuffle_reduce(self._Sum(), k2, mk, vals, valid, sign,
                                 keys, backend="pallas")
        live = np.asarray(out.live)
        v_s = np.asarray(out.values)[:, 0]
        # exactly one live row per key, and it is the last-arriving copy
        assert live.sum() == n // reps
        np.testing.assert_array_equal(
            v_s[live], np.arange(reps - 1, n, reps, dtype=np.float32))

    def test_tombstone_delete(self):
        from repro.kernels import ops
        k2 = jnp.asarray([3, 3, 5], jnp.int32)
        mk = jnp.asarray([0, 0, 0], jnp.int32)
        vals = jnp.asarray([[1.0], [2.0], [7.0]])
        valid = jnp.ones(3, bool)
        sign = jnp.asarray([1, -1, 1], jnp.int8)   # 3 deleted by tombstone
        keys = jnp.asarray([3, 5] + [2**31 - 1] * 6, jnp.int32)
        for bk in ("xla", "pallas"):
            sr = ops.shuffle_reduce(self._Sum(), k2, mk, vals, valid, sign,
                                    keys, backend=bk)
            counts = np.asarray(sr.counts)
            assert counts[0] == 0 and counts[1] == 1
            assert np.asarray(sr.acc)[1, 0] == 7.0


def _search_route(k2, affected_keys):
    """The reference route, a binary search per row: each row's key
    searched among the affected keys, then looked up."""
    key_cap = affected_keys.shape[0]
    local = jnp.searchsorted(affected_keys, k2).astype(jnp.int32)
    in_set = jnp.take(affected_keys, jnp.clip(local, 0, key_cap - 1)) == k2
    return local, in_set


def _route_case(name):
    """(k2, mk, values, valid, sign, affected_keys) of one route case."""
    from repro.serve.batch import MAX_GLOBAL_KEY
    rng = np.random.default_rng(len(name))
    n, nkeys, cap, pad = 300, 40, 64, 2**31 - 1
    k2 = rng.integers(0, nkeys, n)
    valid = rng.random(n) < 0.8
    aff = np.unique(k2[valid])
    if name == "absent_keys":           # empty runs below, among, above
        aff = np.concatenate([[-5], np.arange(0, nkeys, 2), [nkeys + 9]])
    elif name == "all_invalid":
        valid[:] = False
    elif name == "n_eq_key_cap":        # one row per slot
        n = cap
        k2 = rng.permutation(2 * cap)[:n]
        valid = np.ones(n, bool)
        aff = np.sort(k2)
    elif name == "runs_at_both_ends":   # first and last rows in runs
        valid[:] = True
        aff = np.unique(k2)
    elif name == "serve_global_keys":   # tenant lanes, serve's pad key
        tenant = rng.integers(0, 3, n)
        k2 = k2 + tenant * (1 << 28)
        aff = np.unique(k2[valid])[::2]
        pad = MAX_GLOBAL_KEY
    keys = np.full(cap, pad, np.int32)
    keys[:aff.size] = aff
    mk = rng.integers(0, 8, n)
    vals = rng.integers(-20, 20, (n, 2)).astype(np.float32)
    sign = np.where(rng.random(n) < 0.75, 1, -1).astype(np.int8)
    return tuple(jnp.asarray(a) for a in (k2.astype(np.int32),
                                          mk.astype(np.int32), vals, valid,
                                          sign, keys))


ROUTE_CASES = ["absent_keys", "all_invalid", "n_eq_key_cap",
               "runs_at_both_ends", "int32_max_pads", "serve_global_keys"]


class TestRoute:
    """The route from run boundaries (``ops._route``) against a binary
    search per row: the same slot and membership for every row,
    and ``ops.shuffle_reduce`` through either bitwise equal on every
    output, on both backends."""

    class _Sum:
        kind = "sum"

    @pytest.mark.parametrize("case", ROUTE_CASES)
    def test_route_matches_search_on_every_row(self, case):
        from repro.kernels import ops
        k2, _, _, valid, _, keys = _route_case(case)
        rows = jnp.sort(jnp.where(valid, k2, jnp.int32(2**31 - 1)))
        for got, want, name in zip(ops._route(rows, keys),
                                   _search_route(rows, keys),
                                   ("local", "in_set")):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=name)

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    @pytest.mark.parametrize("case", ROUTE_CASES)
    def test_shuffle_reduce_matches_search_route(self, case, backend,
                                                 monkeypatch):
        from repro.kernels import ops
        args = _route_case(case)
        got = ops.shuffle_reduce(self._Sum(), *args, backend=backend)
        monkeypatch.setattr(ops, "_route", _search_route)
        want = ops.shuffle_reduce(self._Sum(), *args, backend=backend)
        for name in ops.ShuffleReduced._fields:
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)

    def test_no_gather_per_row(self):
        """The compiled merge's route gathers only key_cap results (the
        searches of the keys in the rows), never one per row: the per-row
        search stays gone."""
        from repro.core.incremental import _merge_reduce
        from repro.core.kvstore import Edges, sum_reducer
        n, key_cap = 1 << 14, 1024
        lane = lambda dt: jax.ShapeDtypeStruct((n,), dt)
        combined = Edges(lane(jnp.int32), lane(jnp.int32),
                         {"c": lane(jnp.float32)}, lane(jnp.bool_),
                         lane(jnp.int8))
        text = _merge_reduce.lower(
            sum_reducer(), key_cap, "xla", combined,
            jax.ShapeDtypeStruct((key_cap,), jnp.int32)).compile().as_text()
        shapes = [re.search(r"= \w+\[([\d,]*)\]", line).group(1)
                  for line in text.splitlines()
                  if " gather(" in line and "/shuffle_reduce/route/" in line]
        assert shapes, "no gather in the route scope: the searches moved?"
        for dims in shapes:
            rows = np.prod([int(d) for d in dims.split(",") if d])
            assert rows <= key_cap, f"route gathers [{dims}]"


def _gathered_shuffle_reduce(reducer, k2, mk, values, valid, sign,
                             affected_keys, backend):
    """The reference merge: ``valid`` and ``sign`` gathered through the
    sort permutation as payload leaves, the rest as in
    ``ops.shuffle_reduce``."""
    from repro.kernels import ops
    n, key_cap = k2.shape[0], affected_keys.shape[0]
    k2m = jnp.where(valid, k2, jnp.int32(2**31 - 1))
    res = ops.sort_pairs(k2m, mk, (values, valid, sign), num_keys=2,
                         backend=backend)
    vals_s, valid_s, sign_s = res.payload
    is_last = ((jnp.arange(n) == n - 1) | (jnp.roll(res.k2, -1) != res.k2)
               | (jnp.roll(res.mk, -1) != res.mk))
    live = valid_s & is_last & (sign_s > 0)
    local, in_set = ops._route(res.k2, affected_keys)
    acc, counts = ops.segment_reduce(reducer, local, vals_s, live & in_set,
                                     key_cap, backend=backend)
    return ops.ShuffleReduced(res.k2, res.mk, vals_s, live, res.perm, acc,
                              counts)


def _flag_case(name):
    """A route case, or tombstones and invalid rows interleaved over
    repeated (k2, mk) pairs, so that every flag decides some row."""
    if name != "tombstones_interleaved":
        return _route_case(name)
    rng = np.random.default_rng(7)
    n, cap = 300, 64
    k2 = rng.integers(0, 20, n).astype(np.int32)
    mk = rng.integers(0, 3, n).astype(np.int32)
    valid = np.arange(n) % 3 != 1
    sign = np.where(np.arange(n) % 2 == 0, 1, -1).astype(np.int8)
    vals = rng.integers(-20, 20, (n, 2)).astype(np.float32)
    keys = np.full(cap, 2**31 - 1, np.int32)
    aff = np.unique(k2[valid])
    keys[:aff.size] = aff
    return tuple(jnp.asarray(a) for a in (k2, mk, vals, valid, sign, keys))


class TestMergeFlags:
    """``ops.shuffle_reduce`` with ``valid`` and ``sign > 0`` carried by
    the sort equals the composition that gathered them, bitwise on every
    output and on both backends."""

    class _Sum:
        kind = "sum"

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    @pytest.mark.parametrize("case", ROUTE_CASES + ["tombstones_interleaved"])
    def test_matches_gathered_flags(self, case, backend):
        from repro.kernels import ops
        args = _flag_case(case)
        got = ops.shuffle_reduce(self._Sum(), *args, backend=backend)
        want = _gathered_shuffle_reduce(self._Sum(), *args, backend)
        for name in ops.ShuffleReduced._fields:
            np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                          np.asarray(getattr(want, name)),
                                          err_msg=name)
        if case == "tombstones_interleaved":     # the flags decide rows
            live = np.asarray(got.live)
            assert 0 < live.sum() < (np.asarray(args[3])).sum()


class TestSpmv:
    @pytest.mark.parametrize("s,f,v", [(100, 4, 50), (500, 6, 700),
                                       (256, 8, 1024)])
    def test_sweep(self, s, f, v):
        rng = np.random.default_rng(s)
        nbrs = rng.integers(0, v, (s, f))
        nbrs[rng.random((s, f)) < 0.3] = -1
        contrib = rng.normal(0, 1, (s, f)).astype(np.float32)
        got = spmv_ell(jnp.asarray(nbrs, jnp.int32), jnp.asarray(contrib),
                       v, rows=64, kblk=256)
        want = spmv_ell_ref(jnp.asarray(nbrs), jnp.asarray(contrib), v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_peaks_keyed_by_device_kind():
    from repro.launch.mesh import PEAK_FLOPS, V5E, peaks
    assert peaks(V5E)["flops_bf16"] == PEAK_FLOPS == 197e12
    assert peaks(V5E)["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")

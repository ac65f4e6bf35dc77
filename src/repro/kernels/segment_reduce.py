"""Segment reduction on the MXU (the Reduce stage on TPU).

Hadoop's Reduce iterates a key's value list with scalar code; a TPU wants
matrix units.  For a tile of R rows with segment ids ``seg[R]`` and values
``vals[R, D]``, the per-tile contribution to the output block [K, D] is

    onehot(seg)[R, K]^T @ vals[R, D]     (one 128x128-aligned MXU matmul)

The grid walks (output blocks x row tiles) with the row tiles innermost;
each output block stays resident in VMEM across its row-tile loop
(BlockSpec index_map pins it), accumulating partial sums — the classic
stationary-output tiling.  The order matters on the chip: Pallas writes
an output block back when its index changes and never reads it back, so
the reduction axis must be the innermost one.

Three kernel families cover all four ``Reducer`` monoids:

  * ``segment_sum_mxu``        — sum and mean (mean = sum + count, the
    division happens in ``kvstore.finalize_reduce``); integer values
    accumulate exactly in int32 (byte limbs on the MXU, see
    ``_onehot_dot``), floats in float32.
  * ``segment_sum_counts_mxu`` — the same matmul with the per-segment row
    counts as a second output of the *same* launch (counts are the one-hot
    column sums, already resident), so the dispatcher's (acc, counts)
    contract costs one kernel instead of two.
  * ``segment_minmax_mxu``     — min/max via a *sublane* reduction: rows
    stream through in aligned chunks of ``SUBLANES`` (the VPU's 8-row
    register height), each row folded into the rows of a stationary
    [kblk, D] accumulator that its segment id selects.
  * ``segment_reduce_mxu``     — the original float32 sum entry point,
    kept as the benchmark/back-compat surface.

Degenerate inputs (no rows, no segments) return empty/identity results
instead of tripping the tiling math.  ``interpret`` defaults to platform
auto-detection (``REPRO_PALLAS_INTERPRET`` overrides).  ``repro.kernels.
ref`` holds the pure-jnp oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import jitcache
from repro.kernels.ref import segment_minmax_ref, segment_reduce_ref  # noqa: F401
from repro.kernels.sort_u32 import default_interpret

DEFAULT_ROWS = 1024     # rows per tile
DEFAULT_KBLK = 256      # output segments per block: small blocks make the
                        # sorted-input block-skip (see _block_live) bite
MINMAX_ROWS = DEFAULT_ROWS   # sublane kernel: no cubic intermediate to cap
MINMAX_KBLK = DEFAULT_KBLK
SUBLANES = 8            # VPU register height: min/max chunk size


def _block_live(seg, base: int, kblk: int):
    """True iff any row of this tile lands in output block [base, base+kblk).

    The shuffle feeds the reducer *sorted* segment ids, so most
    (row tile x output block) grid pairs are empty; gating the matmul on
    this cheap VPU range test turns the grid from dense O(n/R * K/kblk)
    matmuls into the ~O(n/R + K/kblk) non-empty band.  Unsorted ids stay
    correct — the test is exact, just less often false.
    """
    return jnp.any((seg >= base) & (seg < base + kblk))


INT_ROWS_MAX = 1 << 16  # integer sums: a tile's byte-limb sums stay < 2**24


def _onehot_dot(onehot, vals, out_dtype):
    """``onehot[R, K]^T @ vals[R, D]`` on the MXU, in ``out_dtype``.

    Floats run one matmul (float32 at full precision).  The MXU takes no
    int32 operands, so int32 values are split into four unsigned byte
    limbs, each an exact bf16; each limb's per-tile sum (at most
    ``rows * 255 < 2**24``) is exact in the float32 accumulator, and the
    limbs recombine with wrapping int32 shifts.  The result is the int32
    sum modulo 2**32, bit for bit what ``jax.ops.segment_sum`` gives.
    """
    if not jnp.issubdtype(out_dtype, jnp.integer):
        precision = (jax.lax.Precision.HIGHEST if vals.dtype == jnp.float32
                     else None)
        return jnp.dot(onehot.astype(vals.dtype).T, vals,
                       preferred_element_type=out_dtype, precision=precision)
    oh = onehot.astype(jnp.bfloat16).T
    acc = None
    for b in range(4):
        limb = jnp.bitwise_and(jnp.right_shift(vals, 8 * b), 0xFF)
        part = jnp.dot(oh, limb.astype(jnp.float32).astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        part = jnp.left_shift(part.astype(jnp.int32), 8 * b)
        acc = part if acc is None else acc + part
    return acc


def _sum_kernel(seg_ref, val_ref, out_ref, *, kblk: int, rows: int):
    i = pl.program_id(1)      # row tile (innermost: the reduction axis)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...]                        # [rows]
    base = pl.program_id(0) * kblk

    @pl.when(_block_live(seg, base, kblk))
    def _work():
        local = seg - base
        onehot = (local[:, None] ==
                  jax.lax.broadcasted_iota(jnp.int32, (rows, kblk), 1))
        out_ref[...] += _onehot_dot(onehot, val_ref[...], out_ref.dtype)


def _sum_counts_kernel(seg_ref, val_ref, out_ref, cnt_ref, *, kblk: int,
                       rows: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    seg = seg_ref[...]
    base = pl.program_id(0) * kblk

    @pl.when(_block_live(seg, base, kblk))
    def _work():
        local = seg - base
        onehot = (local[:, None] ==
                  jax.lax.broadcasted_iota(jnp.int32, (rows, kblk), 1))
        # int32 column sums on the VPU: exact past 2**24 rows per segment
        cnt_ref[...] += jnp.sum(onehot.astype(jnp.int32), axis=0)[:, None]
        out_ref[...] += _onehot_dot(onehot, val_ref[...], out_ref.dtype)


def _minmax_kernel(seg_ref, val_ref, out_ref, *, kblk: int, rows: int,
                   is_min: bool, ident):
    """Rows stream through in aligned chunks of ``SUBLANES``; each row of a
    chunk folds into the accumulator rows of its segment.  ``seg`` is a
    ``[rows, 1]`` column so a chunk is one aligned sublane slice."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, ident)

    base = pl.program_id(0) * kblk
    d = val_ref.shape[1]
    dtype = val_ref.dtype
    fold = jnp.minimum if is_min else jnp.maximum
    kcol = base + jax.lax.broadcasted_iota(jnp.int32, (kblk, 1), 0)

    @pl.when(_block_live(seg_ref[...], base, kblk))
    def _work():
        def chunk(c, acc):
            r0 = pl.multiple_of(c * SUBLANES, SUBLANES)
            seg8 = seg_ref[pl.ds(r0, SUBLANES), :]        # [8, 1]
            vals8 = val_ref[pl.ds(r0, SUBLANES), :]       # [8, D]
            for r in range(SUBLANES):
                hit = kcol == seg8[r:r + 1, :]            # [kblk, 1]
                acc = jnp.where(hit, fold(acc, vals8[r:r + 1, :]), acc)
            return acc

        acc0 = jnp.full((kblk, d), ident, dtype)
        acc = jax.lax.fori_loop(0, rows // SUBLANES, chunk, acc0)
        out_ref[...] = fold(out_ref[...], acc)


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def padded_rows(n: int, rows: int = DEFAULT_ROWS) -> int:
    """The least row count >= ``n`` that every kernel here tiles without
    padding: a multiple of the row tile, or of ``SUBLANES`` below one."""
    return _round_up(n, rows if n >= rows else SUBLANES)


def _pad_rows(seg, vals, rows, num_segments, *, fill=0, multiple=1):
    """Clamp the row tile to the (padded) input and pad rows to a multiple.

    Callers guarantee ``n > 0``; padding rows carry segment id
    ``num_segments`` (the scratch segment) and ``fill`` values.
    """
    n, d = vals.shape
    rows = max(multiple, _round_up(min(rows, n), multiple))
    if n % rows != 0:
        pad = rows - n % rows
        seg = jnp.concatenate([seg, jnp.full(pad, num_segments, seg.dtype)])
        vals = jnp.concatenate([vals, jnp.full((pad, d), fill, vals.dtype)])
    return seg, vals, rows


def _kblocks(num_segments, kblk):
    kblk = min(kblk, max(num_segments, 1))
    kpad = (kblk - num_segments % kblk) % kblk
    return kblk, num_segments + kpad


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "out_dtype", "rows",
                                    "kblk", "interpret"))
def segment_sum_mxu(seg: jax.Array, vals: jax.Array, num_segments: int, *,
                    out_dtype=jnp.float32, rows: int = DEFAULT_ROWS,
                    kblk: int = DEFAULT_KBLK,
                    interpret: bool | None = None) -> jax.Array:
    """seg [N] int32 (invalid rows: any id >= num_segments), vals [N, D].

    Returns [num_segments, D] sums in ``out_dtype``.  Padding rows outside
    [0, num_segments) may land in the kblk overhang; the slice drops them.
    """
    if interpret is None:
        interpret = default_interpret()
    n, d = vals.shape
    if num_segments <= 0:
        return jnp.zeros((max(num_segments, 0), d), out_dtype)
    if n == 0:
        return jnp.zeros((num_segments, d), out_dtype)
    if jnp.issubdtype(out_dtype, jnp.integer):
        rows = min(rows, INT_ROWS_MAX)
    seg, vals, rows = _pad_rows(seg, vals, rows, num_segments)
    n, d = vals.shape
    kblk, kfull = _kblocks(num_segments, kblk)
    if jnp.issubdtype(vals.dtype, jnp.integer):
        vals = vals.astype(out_dtype)
    jitcache.count_trace("kernels.segment_sum")
    out = pl.pallas_call(
        functools.partial(_sum_kernel, kblk=kblk, rows=rows),
        grid=(kfull // kblk, n // rows),
        in_specs=[
            pl.BlockSpec((rows,), lambda j, i: (i,)),
            pl.BlockSpec((rows, d), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((kblk, d), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((kfull, d), out_dtype),
        interpret=interpret,
    )(seg.astype(jnp.int32), vals)
    return out[:num_segments]


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "out_dtype", "rows",
                                    "kblk", "interpret"))
def segment_sum_counts_mxu(seg: jax.Array, vals: jax.Array,
                           num_segments: int, *, out_dtype=jnp.float32,
                           rows: int = DEFAULT_ROWS,
                           kblk: int = DEFAULT_KBLK,
                           interpret: bool | None = None):
    """One launch for the dispatcher's (sums [K, D], counts [K]) contract.

    ``counts`` are the one-hot column sums — exactly what
    ``jax.ops.segment_sum(ones)`` would produce, without re-reading the
    segment ids from HBM in a second kernel.
    """
    if interpret is None:
        interpret = default_interpret()
    n, d = vals.shape
    if num_segments <= 0:
        k = max(num_segments, 0)
        return (jnp.zeros((k, d), out_dtype), jnp.zeros(k, jnp.int32))
    if n == 0:
        return (jnp.zeros((num_segments, d), out_dtype),
                jnp.zeros(num_segments, jnp.int32))
    if jnp.issubdtype(out_dtype, jnp.integer):
        rows = min(rows, INT_ROWS_MAX)
    seg, vals, rows = _pad_rows(seg, vals, rows, num_segments)
    n, d = vals.shape
    kblk, kfull = _kblocks(num_segments, kblk)
    if jnp.issubdtype(vals.dtype, jnp.integer):
        vals = vals.astype(out_dtype)
    jitcache.count_trace("kernels.segment_sum_counts")
    out, cnt = pl.pallas_call(
        functools.partial(_sum_counts_kernel, kblk=kblk, rows=rows),
        grid=(kfull // kblk, n // rows),
        in_specs=[
            pl.BlockSpec((rows,), lambda j, i: (i,)),
            pl.BlockSpec((rows, d), lambda j, i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((kblk, d), lambda j, i: (j, 0)),
                   pl.BlockSpec((kblk, 1), lambda j, i: (j, 0))],
        out_shape=[jax.ShapeDtypeStruct((kfull, d), out_dtype),
                   jax.ShapeDtypeStruct((kfull, 1), jnp.int32)],
        interpret=interpret,
    )(seg.astype(jnp.int32), vals)
    return out[:num_segments], cnt[:num_segments, 0]


@functools.partial(jax.jit,
                   static_argnames=("kind", "num_segments", "rows", "kblk",
                                    "interpret"))
def segment_minmax_mxu(kind: str, seg: jax.Array, vals: jax.Array,
                       num_segments: int, *, rows: int = MINMAX_ROWS,
                       kblk: int = MINMAX_KBLK,
                       interpret: bool | None = None) -> jax.Array:
    """Segment min/max; empty segments hold the reduction identity."""
    assert kind in ("min", "max"), kind
    if interpret is None:
        interpret = default_interpret()
    if jnp.issubdtype(vals.dtype, jnp.floating):
        # XLA's segment_min/max identity for empty float segments is ±inf
        ident = float("inf") if kind == "min" else float("-inf")
    else:
        info = jnp.iinfo(vals.dtype)
        ident = info.max if kind == "min" else info.min
    n, d = vals.shape
    if num_segments <= 0:
        return jnp.full((max(num_segments, 0), d), ident, vals.dtype)
    if n == 0:
        return jnp.full((num_segments, d), ident, vals.dtype)
    # pad rows with the identity (not zero) so padding never wins, and to a
    # sublane multiple so the chunked scan tiles evenly
    seg, vals, rows = _pad_rows(seg, vals, rows, num_segments, fill=ident,
                                multiple=SUBLANES)
    n, d = vals.shape
    kblk, kfull = _kblocks(num_segments, kblk)
    jitcache.count_trace("kernels.segment_minmax")
    out = pl.pallas_call(
        functools.partial(_minmax_kernel, kblk=kblk, rows=rows,
                          is_min=(kind == "min"), ident=ident),
        grid=(kfull // kblk, n // rows),
        in_specs=[
            pl.BlockSpec((rows, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((rows, d), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((kblk, d), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((kfull, d), vals.dtype),
        interpret=interpret,
    )(seg.astype(jnp.int32)[:, None], vals)
    return out[:num_segments]


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "rows", "kblk",
                                    "interpret"))
def segment_reduce_mxu(seg: jax.Array, vals: jax.Array, num_segments: int,
                       *, rows: int = DEFAULT_ROWS, kblk: int = DEFAULT_KBLK,
                       interpret: bool | None = None) -> jax.Array:
    """Original float32-sum entry point (benchmarks, back-compat)."""
    return segment_sum_mxu(seg, vals.astype(jnp.float32), num_segments,
                           out_dtype=jnp.float32, rows=rows, kblk=kblk,
                           interpret=interpret)

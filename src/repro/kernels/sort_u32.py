"""Multi-tile bitonic sort of key/payload lanes — the shuffle-sort on TPU.

Hadoop's shuffle sorts spill files with comparison mergesort on the CPU;
the TPU analogue is a data-parallel bitonic network over VMEM-resident
tiles: log²(T) compare-exchange stages, each a vectorized select between a
tile and its stride-permuted self (no data-dependent control flow, VPU
friendly).  Each lane is held as a ``(rows, 128)`` tile in row-major
order, so a stage's partner is a lane rotate (distance < 128) or a
sublane rotate (distance >= 128) — both native on the VPU.

The network sorts three int lanes lexicographically: a primary key, a
secondary key, and the original row index.  Because the index lane is
unique, the comparison is a total order — which makes the (otherwise
unstable) bitonic network *stable* with respect to (primary, secondary)
and lets the index lane double as the output permutation.  The engine's
merge path (``incremental._merge_reduce``) depends on exactly this
stability for its last-writer-wins semantics.

A few flag bits per row ride under the index lane for free: the lane
holds ``(row << tag_bits) | tag``, which is still unique and orders
exactly as ``row`` does, so the network returns the same permutation
and carries the tag through every compare-exchange stage with the lane
it moves anyway.  Other payloads are gathered once through the
permutation instead of riding through every stage.

Inputs larger than one VMEM tile are handled by splitting the global
bitonic network at tile granularity (``SORT_TILE`` rows per tile):

  * a per-tile pass runs every stage with compare distance ``j < tile``
    entirely in VMEM (directions follow the *global* position, so each
    tile computes its slice of the one global network);
  * each stage with ``j >= tile`` pairs whole tiles (partner tile =
    ``tile_index XOR j/tile``) and becomes one grid launch over tile
    pairs, two tiles resident in VMEM per step.

Total work stays the bitonic O(n log² n) while VMEM is bounded by the
tile size.  Inputs that fit one tile run the whole network in one
launch; every input is padded to at least ``MIN_TILE`` rows, one
(8, 128) int32 vreg.

``interpret`` defaults to auto-detection (interpret off TPU, native on
TPU); set ``REPRO_PALLAS_INTERPRET=0/1`` to override.  ``repro.kernels.
ref`` holds the pure-jnp oracles.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import jitcache
from repro.kernels.ref import sort_kv32_ref  # noqa: F401  (back-compat)

SORT_TILE = 4096        # rows per VMEM tile (power of two)
LANES = 128             # lane width of a vreg: tiles are (rows, LANES)
MIN_TILE = 8 * LANES    # one (8, 128) int32 vreg: the smallest tile


def default_interpret() -> bool:
    """Pallas interpret mode everywhere but real TPU.

    ``REPRO_PALLAS_INTERPRET=1`` forces interpret mode on TPU (debugging);
    ``REPRO_PALLAS_INTERPRET=0`` forces native lowering off TPU (fails
    loudly where Mosaic is unavailable — useful for lowering checks).
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None and env != "":
        if env.lower() in ("1", "true", "yes", "on"):
            return True
        if env.lower() in ("0", "false", "no", "off"):
            return False
        raise ValueError(
            f"REPRO_PALLAS_INTERPRET must be boolean-like, got {env!r}")
    return jax.default_backend() != "tpu"


def _lex_lt(ah, al, ai, bh, bl, bi):
    """(ah, al, ai) < (bh, bl, bi) lexicographically.

    Pure boolean algebra: Mosaic has no select between boolean vectors.
    """
    return (ah < bh) | ((ah == bh) & ((al < bl) | ((al == bl) & (ai < bi))))


def _partner(x, j: int, coord, axis: int, size: int):
    """``x`` at tile position ``p XOR j`` along ``axis`` (lanes or rows).

    Two cyclic rolls bring both candidates (``p + j`` and ``p - j``) to
    ``p``; rolling the coordinate iota the same way picks the one that is
    the partner, so the result does not depend on the roll's direction
    convention.  Mosaic lowers both rolls natively (lane and sublane
    rotates), which a data-dependent gather does not.
    """
    fwd = pltpu.roll(x, j, axis)
    if 2 * j == size:                 # both rolls coincide
        return fwd
    bwd = pltpu.roll(x, size - j, axis)
    src = pltpu.roll(coord, j, axis)
    return jnp.where(src == jnp.bitwise_xor(coord, j), fwd, bwd)


def _stage(hi, lo, idx, j, k, base):
    """One intra-tile compare-exchange stage of the *global* network.

    Lanes are ``(rows, 128)`` tiles in row-major order (position
    ``row * 128 + lane``).  A partner at distance ``j < 128`` sits in the
    same row; one at ``j >= 128`` sits ``j / 128`` rows away in the same
    lane.  ``base`` is the tile's global row offset: directions are a
    function of global position, which is what lets independently
    launched tiles each compute their slice of one coherent network.
    """
    rows = hi.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, hi.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, hi.shape, 1)
    pos = row * LANES + lane
    if j < LANES:
        take = functools.partial(_partner, j=j, coord=lane, axis=1,
                                 size=LANES)
    else:
        take = functools.partial(_partner, j=j // LANES, coord=row, axis=0,
                                 size=rows)
    ph, plo, pi = take(hi), take(lo), take(idx)
    up = (jnp.bitwise_and(base + pos, k) == 0)   # ascending region?
    is_lo = jnp.bitwise_and(pos, j) == 0
    want_min = up == is_lo
    own_lt = _lex_lt(hi, lo, idx, ph, plo, pi)   # never equal: idx is unique
    take_own = want_min == own_lt
    sel = lambda a, b: jnp.where(take_own, a, b)
    return sel(hi, ph), sel(lo, plo), sel(idx, pi)


def _tile_sort_kernel(hi_ref, lo_ref, idx_ref, ho_ref, lo_out_ref, po_ref, *,
                      tile: int):
    """Stages k = 2..tile of the global network, one tile in VMEM."""
    base = pl.program_id(0) * tile
    hi = hi_ref[...]
    lo = lo_ref[...]
    idx = idx_ref[...]
    k = 2
    while k <= tile:
        j = k // 2
        while j >= 1:
            hi, lo, idx = _stage(hi, lo, idx, j, k, base)
            j //= 2
        k *= 2
    ho_ref[...] = hi
    lo_out_ref[...] = lo
    po_ref[...] = idx


def _tile_finish_kernel(hi_ref, lo_ref, idx_ref, ho_ref, lo_out_ref, po_ref,
                        *, tile: int, k: int):
    """Stages j = tile/2..1 of round ``k`` (> tile), one tile in VMEM."""
    base = pl.program_id(0) * tile
    hi = hi_ref[...]
    lo = lo_ref[...]
    idx = idx_ref[...]
    j = tile // 2
    while j >= 1:
        hi, lo, idx = _stage(hi, lo, idx, j, k, base)
        j //= 2
    ho_ref[...] = hi
    lo_out_ref[...] = lo
    po_ref[...] = idx


def _cross_kernel(ahi_ref, alo_ref, ai_ref, bhi_ref, blo_ref, bi_ref,
                  oh_ref, ol_ref, oi_ref, *, tile: int, k: int, dt: int):
    """One cross-tile stage (compare distance j = dt * tile).

    The grid runs over (tile pair, side): a pair's lower tile holds global
    positions ``p`` and its upper tile ``p XOR j``, so the stage is a pure
    elementwise compare-exchange between the two resident tiles.  The
    ``side`` grid axis selects which half the step writes (a BlockSpec
    maps one block per step), with both tiles resident either way.
    """
    p = pl.program_id(0)
    side = pl.program_id(1)                        # 0 = lower, 1 = upper
    lo_tile = (p // dt) * (2 * dt) + (p % dt)
    up = jnp.bitwise_and(lo_tile * tile, k) == 0   # scalar: whole tile
    ah, al, ai = ahi_ref[...], alo_ref[...], ai_ref[...]
    bh, bl, bi = bhi_ref[...], blo_ref[...], bi_ref[...]
    a_lt = _lex_lt(ah, al, ai, bh, bl, bi)         # never equal
    take_a = up == a_lt                            # lower position keeps min
    want_a = take_a == (side == 0)                 # upper side keeps the rest
    oh_ref[...] = jnp.where(want_a, ah, bh)
    ol_ref[...] = jnp.where(want_a, al, bl)
    oi_ref[...] = jnp.where(want_a, ai, bi)


def _lane_specs(tile: int, index_map):
    return [pl.BlockSpec((tile // LANES, LANES), index_map)
            for _ in range(3)]


def _lane_shapes(m: int, hi_dtype, lo_dtype):
    shape = (m // LANES, LANES)
    return [jax.ShapeDtypeStruct(shape, hi_dtype),
            jax.ShapeDtypeStruct(shape, lo_dtype),
            jax.ShapeDtypeStruct(shape, jnp.int32)]


_INT32_MAX = 2**31 - 1


@functools.partial(jax.jit,
                   static_argnames=("tile", "interpret", "tag_bits"))
def sorted_lanes(hi: jax.Array, lo: jax.Array, tag: jax.Array | None = None,
                 *, tile: int, interpret: bool, tag_bits: int = 0):
    """Sort pre-padded (hi, lo) lanes of length ``m``, a power of two of at
    least ``MIN_TILE``, with the row index as the third lane; lengths past
    ``tile`` run the multi-tile network.  A ``tag`` of ``tag_bits`` bits
    rides under the index, ``(row << tag_bits) | tag``.  Returns ``(hi, lo,
    perm, tag)`` flat (``tag`` None without one); the kernels see each
    lane as ``(m / 128, 128)`` tiles.
    """
    jitcache.count_trace("kernels.sort_lex")
    m = hi.shape[0]
    if (m << tag_bits) - 1 > _INT32_MAX:     # the largest tagged index
        raise ValueError(
            f"{m} padded rows with {tag_bits} tag bits overflow the int32 "
            f"index lane: at most {(_INT32_MAX + 1) >> tag_bits} rows")
    idx = jnp.arange(m, dtype=jnp.int32)
    if tag_bits:
        idx = jnp.bitwise_or(jnp.left_shift(idx, tag_bits), tag)
    hi, lo, idx = _network(hi, lo, idx, tile=tile, interpret=interpret)
    if not tag_bits:
        return hi, lo, idx, None
    return (hi, lo, jnp.right_shift(idx, tag_bits),
            jnp.bitwise_and(idx, (1 << tag_bits) - 1))


def _network(hi, lo, idx, *, tile: int, interpret: bool):
    """The bitonic network over flat lanes of a power-of-two length."""
    m = hi.shape[0]
    hi, lo, idx = (a.reshape(m // LANES, LANES) for a in (hi, lo, idx))
    if m <= tile:
        # single tile: the whole network in one launch
        hi, lo, idx = pl.pallas_call(
            functools.partial(_tile_sort_kernel, tile=m),
            grid=(1,),
            in_specs=_lane_specs(m, lambda i: (0, 0)),
            out_specs=_lane_specs(m, lambda i: (0, 0)),
            out_shape=_lane_shapes(m, hi.dtype, lo.dtype),
            interpret=interpret,
        )(hi, lo, idx)
        return hi.reshape(m), lo.reshape(m), idx.reshape(m)

    tiles = m // tile
    per_tile = lambda i: (i, 0)
    hi, lo, idx = pl.pallas_call(
        functools.partial(_tile_sort_kernel, tile=tile),
        grid=(tiles,),
        in_specs=_lane_specs(tile, per_tile),
        out_specs=_lane_specs(tile, per_tile),
        out_shape=_lane_shapes(m, hi.dtype, lo.dtype),
        interpret=interpret,
    )(hi, lo, idx)

    k = tile * 2
    while k <= m:
        j = k // 2
        while j >= tile:
            dt = j // tile
            lo_map = lambda p, s, dt=dt: ((p // dt) * (2 * dt) + (p % dt), 0)
            hi_map = lambda p, s, dt=dt: (
                (p // dt) * (2 * dt) + (p % dt) + dt, 0)
            out_map = lambda p, s, dt=dt: (
                (p // dt) * (2 * dt) + (p % dt) + s * dt, 0)
            hi, lo, idx = pl.pallas_call(
                functools.partial(_cross_kernel, tile=tile, k=k, dt=dt),
                grid=(tiles // 2, 2),
                in_specs=_lane_specs(tile, lo_map) + _lane_specs(tile, hi_map),
                out_specs=_lane_specs(tile, out_map),
                out_shape=_lane_shapes(m, hi.dtype, lo.dtype),
                interpret=interpret,
            )(hi, lo, idx, hi, lo, idx)
            j //= 2
        hi, lo, idx = pl.pallas_call(
            functools.partial(_tile_finish_kernel, tile=tile, k=k),
            grid=(tiles,),
            in_specs=_lane_specs(tile, per_tile),
            out_specs=_lane_specs(tile, per_tile),
            out_shape=_lane_shapes(m, hi.dtype, lo.dtype),
            interpret=interpret,
        )(hi, lo, idx)
        k *= 2
    return hi.reshape(m), lo.reshape(m), idx.reshape(m)


def _type_max(dtype):
    return jnp.iinfo(dtype).max


def padded_length(n: int) -> int:
    """Pad policy: the next power of two, and at least one ``MIN_TILE``
    (the bitonic network needs a power-of-two total; past one tile that
    is a power-of-two count of tiles)."""
    m = MIN_TILE
    while m < n:
        m *= 2
    return m


def pad_lanes(hi: jax.Array, lo: jax.Array, m: int):
    """Pad both key lanes to ``m`` with their dtype max (sorts to the tail)."""
    n = hi.shape[0]
    if m == n:
        return hi, lo
    hi = jnp.concatenate([hi, jnp.full(m - n, _type_max(hi.dtype), hi.dtype)])
    lo = jnp.concatenate([lo, jnp.full(m - n, _type_max(lo.dtype), lo.dtype)])
    return hi, lo


def sort_lex_pallas(hi: jax.Array, lo: jax.Array, *, tile: int = SORT_TILE,
                    interpret: bool | None = None,
                    tag: jax.Array | None = None, tag_bits: int = 0):
    """Stable lexicographic sort by (hi, lo); ties broken by row index.

    Returns ``(hi_sorted, lo_sorted, perm)`` where ``perm`` is the int32
    permutation (``hi_sorted == hi[perm]``).  Length is padded to
    :func:`padded_length` with both key lanes at their dtype max, so
    padding lands at the tail and ``perm[:n]`` is a permutation of
    ``range(n)``.  Inputs beyond ``tile`` rows run the multi-tile network:
    VMEM stays bounded by the tile size (two tiles per cross-stage launch)
    instead of the whole padded input.  The network is compiled once per
    padded length, not once per input length.

    With an int32 ``tag`` of ``tag_bits`` bits (values in ``[0,
    2**tag_bits)``) the tag rides under the index lane and comes back
    sorted as a fourth output, ``tag[perm]``, with the same permutation;
    a padded length past ``2**31 >> tag_bits`` rows raises ValueError.
    """
    if interpret is None:
        interpret = default_interpret()
    if tile & (tile - 1) or tile < MIN_TILE:
        raise ValueError(
            f"tile must be a power of two >= {MIN_TILE}, got {tile}")
    if tag_bits < 0 or (tag is None) != (tag_bits == 0):
        raise ValueError(
            f"a tag needs tag_bits > 0, and tag_bits a tag, got {tag_bits}")
    n = hi.shape[0]
    m = padded_length(n)
    hi, lo = pad_lanes(hi, lo, m)
    if tag is not None:
        tag = jnp.pad(tag.astype(jnp.int32), (0, m - n))
    ho, lo_out, perm, tag_out = sorted_lanes(
        hi, lo, tag, tile=tile, interpret=interpret, tag_bits=tag_bits)
    if tag is None:
        return ho[:n], lo_out[:n], perm[:n]
    return ho[:n], lo_out[:n], perm[:n], tag_out[:n]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sort_kv32(keys: jax.Array, payload: jax.Array, *, tile: int = SORT_TILE,
              interpret: bool | None = None):
    """Sort uint32/int32 ``keys`` ascending (stable), permuting ``payload``.

    Back-compat single-key entry point over the lexicographic network.
    """
    ko, _, perm = sort_lex_pallas(keys, jnp.zeros_like(keys, jnp.int32),
                                  tile=tile, interpret=interpret)
    return ko, jnp.take(payload, perm, axis=0)

"""Backend dispatch for the engine's two hot paths: shuffle-sort and Reduce.

Every engine layer (one-step, incremental, iterative, incremental-iterative,
distributed) funnels its shuffle and Reduce work through the two entry
points here:

  * :func:`sort_pairs`      — lexicographic stable sort of (k2, mk) with a
    permutation output; up to three bool flags ride the sort itself, and
    arbitrary pytree payloads are gathered once.
  * :func:`segment_reduce`  — segment reduction for all four ``Reducer``
    monoids (sum / min / max / mean) over pytree values, with an explicit
    validity mask and per-segment counts.

Backends:

  * ``"xla"``    — jax.lax.sort / jax.ops.segment_* (the portable fallback).
  * ``"pallas"`` — the Pallas TPU kernels (bitonic network, one-hot MXU
    matmul); interpret mode on CPU, native lowering on TPU.
  * ``"auto"``   — pallas on TPU, xla elsewhere.

Selection precedence: per-call ``backend=`` argument > :func:`set_backend`
(or the :class:`use_backend` context manager) > the ``REPRO_BACKEND``
environment variable > ``"auto"``.  Callers that jit must resolve the
backend *outside* the traced function (``resolve_backend``) and pass it as
a static argument so that flipping the backend retraces instead of hitting
a stale cache — the engine layers all follow this pattern.

Both backends implement the identical contract — same masking semantics,
same tie-breaking (total order by (k2, mk, row index)) — so they agree
bit-for-bit on integer data and to reordering-of-additions on floats;
``tests/test_backend_parity.py`` holds them to it.
"""
from __future__ import annotations

import math
import os
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

BACKENDS = ("xla", "pallas", "auto")
_ENV_VAR = "REPRO_BACKEND"
_configured: Optional[str] = None


def set_backend(name: Optional[str]) -> None:
    """Set the process-wide backend (``None`` reverts to env/auto)."""
    global _configured
    if name is not None and name not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {name!r}")
    _configured = name


def get_backend() -> str:
    """The currently configured (possibly still ``'auto'``) backend."""
    if _configured is not None:
        return _configured
    env = os.environ.get(_ENV_VAR)
    if env:
        if env not in BACKENDS:
            raise ValueError(
                f"{_ENV_VAR} must be one of {BACKENDS}, got {env!r}")
        return env
    return "auto"


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve the per-call override / config / env chain to xla|pallas."""
    b = backend if backend is not None else get_backend()
    if b not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {b!r}")
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "xla"
    return b


class use_backend:
    """Context manager: ``with use_backend('pallas'): ...``"""

    def __init__(self, name: Optional[str]):
        self.name = name
        self.prev: Optional[str] = None

    def __enter__(self):
        global _configured
        self.prev = _configured
        set_backend(self.name)
        return self

    def __exit__(self, *exc):
        global _configured
        _configured = self.prev
        return False


def _interpret() -> bool:
    """Pallas interpret mode everywhere but real TPU.

    Delegates to :func:`repro.kernels.sort_u32.default_interpret`, which
    honors the ``REPRO_PALLAS_INTERPRET`` override.
    """
    from repro.kernels.sort_u32 import default_interpret
    return default_interpret()


# ---------------------------------------------------------------------------
# sort_pairs: the shuffle sort
# ---------------------------------------------------------------------------

class SortedPairs(NamedTuple):
    k2: jax.Array        # [N] sorted primary keys
    mk: jax.Array        # [N] co-sorted secondary keys
    payload: Any         # pytree of [N, ...] gathered through perm
    perm: jax.Array      # [N] int32, k2_sorted == k2[perm]
    flags: tuple = ()    # [N] bool lanes co-sorted by the sort itself


MAX_FLAGS = 3


def sort_pairs(k2: jax.Array, mk: Optional[jax.Array] = None,
               payload: Any = None, *, num_keys: int = 2,
               flags: tuple = (), backend: Optional[str] = None
               ) -> SortedPairs:
    """Stable lexicographic sort by (k2[, mk]); ties keep input order.

    Validity is the caller's concern: mask invalid rows' k2 to INVALID_KEY
    beforehand and they sort to the tail.  ``payload`` may be any pytree of
    [N, ...] arrays; every leaf is gathered once through the permutation.
    ``flags``, at most ``MAX_FLAGS`` bool [N] lanes, come back co-sorted
    without a gather: on pallas they ride in the low bits of the network's
    index lane, on xla they are extra sort operands after the keys.
    """
    bk = resolve_backend(backend)
    n = k2.shape[0]
    flags = tuple(jnp.asarray(f, jnp.bool_) for f in flags)
    if len(flags) > MAX_FLAGS:
        raise ValueError(
            f"at most {MAX_FLAGS} flags ride the sort, got {len(flags)}")
    if mk is None:
        mk = jnp.zeros(n, jnp.int32)
        num_keys = 1
    if bk == "pallas":
        from repro.kernels.sort_u32 import sort_lex_pallas
        lo = mk if num_keys >= 2 else jnp.zeros(n, jnp.int32)
        if flags:
            tag = sum(f.astype(jnp.int32) << i for i, f in enumerate(flags))
            k2s, los, perm, tag = sort_lex_pallas(
                k2, lo, interpret=_interpret(), tag=tag,
                tag_bits=len(flags))
            flags = tuple(((tag >> i) & 1).astype(bool)
                          for i in range(len(flags)))
        else:
            k2s, los, perm = sort_lex_pallas(k2, lo, interpret=_interpret())
        mks = los if num_keys >= 2 else jnp.take(mk, perm, axis=0)
    else:
        iota = jnp.arange(n, dtype=jnp.int32)
        keys = (k2,) if num_keys <= 1 else (k2, mk)
        out = jax.lax.sort(keys + (iota,) + flags, num_keys=len(keys),
                           is_stable=True)
        k2s, perm, flags = out[0], out[len(keys)], tuple(out[len(keys) + 1:])
        mks = jnp.take(mk, perm, axis=0)
    gathered = jax.tree.map(lambda a: jnp.take(a, perm, axis=0), payload)
    return SortedPairs(k2s, mks, gathered, perm, flags)


# ---------------------------------------------------------------------------
# segment_reduce: the Reduce stage
# ---------------------------------------------------------------------------

def _kind_of(reducer) -> str:
    kind = getattr(reducer, "kind", reducer)
    if kind not in ("sum", "min", "max", "mean"):
        raise ValueError(f"unknown reducer kind {kind!r}")
    return kind


def _identity_scalar(kind: str, dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        info = jnp.finfo(dtype)
    else:
        info = jnp.iinfo(dtype)
    return info.max if kind == "min" else info.min


def _mask_leaf(kind: str, leaf: jax.Array, valid: jax.Array) -> jax.Array:
    mask = valid.reshape((-1,) + (1,) * (leaf.ndim - 1))
    if kind in ("min", "max"):
        return jnp.where(mask, leaf, _identity_scalar(kind, leaf.dtype))
    return jnp.where(mask, leaf, 0).astype(leaf.dtype)


def segment_reduce(reducer, segment_ids: jax.Array, values: Any,
                   valid: jax.Array, num_segments: int,
                   indices_are_sorted: bool = False,
                   backend: Optional[str] = None):
    """Reduce ``values`` into ``num_segments`` groups.

    ``reducer`` is a ``repro.core.kvstore.Reducer`` or a bare kind string.
    Returns (accumulated values pytree [K, ...], counts [K] int32); mean
    returns the *sum* (``finalize_reduce`` divides by the counts).  Invalid
    rows are routed to a scratch segment (index ``num_segments``) so they
    never pollute real groups.
    """
    bk = resolve_backend(backend)
    kind = _kind_of(reducer)
    seg = jnp.where(valid, segment_ids, num_segments).astype(jnp.int32)

    if bk == "pallas":
        return _segment_reduce_pallas(kind, seg, values, valid, num_segments)
    return _segment_reduce_xla(kind, seg, values, valid, num_segments,
                               indices_are_sorted)


def _segment_reduce_xla(kind, seg, values, valid, num_segments,
                        indices_are_sorted):
    op = {"sum": jax.ops.segment_sum, "mean": jax.ops.segment_sum,
          "min": jax.ops.segment_min, "max": jax.ops.segment_max}[kind]

    def _one(leaf):
        leaf = _mask_leaf(kind, leaf, valid)
        out = op(leaf, seg, num_segments=num_segments + 1,
                 indices_are_sorted=indices_are_sorted)
        return out[:num_segments]

    acc = jax.tree.map(_one, values)
    counts = jax.ops.segment_sum(valid.astype(jnp.int32), seg,
                                 num_segments=num_segments + 1,
                                 indices_are_sorted=indices_are_sorted)
    return acc, counts[:num_segments]


def _segment_reduce_pallas(kind, seg, values, valid, num_segments):
    from repro.kernels.segment_reduce import (
        padded_rows, segment_minmax_mxu, segment_sum_counts_mxu,
        segment_sum_mxu,
    )
    interp = _interpret()
    # the kernels take [rows, width] value columns, which the TPU pads to
    # 128 lanes (128x at width 1).  Fused into the masking of such a
    # column, the producers of the ids and the mask would have each of
    # their inputs copied into that layout (4 GiB a lane at 2^23 rows, past
    # one v5e's memory in the merge), so both are materialized first
    seg, valid = jax.lax.optimization_barrier((seg, valid))
    # pad the rows while each leaf still has its own shape: the kernels
    # take [rows, width] columns, and on TPU padding a column that was just
    # reshaped from a wider array compiles in time linear in its length
    # (~50 s at 2^19 rows, against ~2 s when padded first)
    pad = padded_rows(seg.shape[0]) - seg.shape[0]
    if pad:
        seg = jnp.pad(seg, (0, pad), constant_values=num_segments)
        valid = jnp.pad(valid, (0, pad))
        values = jax.tree.map(
            lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)),
            values)
    leaves, treedef = jax.tree.flatten(values)
    counts = None
    outs = []
    for leaf in leaves:
        masked = _mask_leaf(kind, leaf, valid)
        width = math.prod(masked.shape[1:])          # -1 breaks on 0 rows
        flat = masked.reshape(masked.shape[0], width)
        if kind in ("sum", "mean"):
            out_dtype = (jnp.int32 if jnp.issubdtype(leaf.dtype, jnp.integer)
                         else jnp.float32)
            if counts is None:
                # the counts ride the first sum leaf's launch for free
                # (one-hot column sums; invalid rows sit in the scratch
                # segment, so segments < num_segments count valid rows only)
                out, cnt = segment_sum_counts_mxu(
                    seg, flat, num_segments + 1, out_dtype=out_dtype,
                    interpret=interp)
                counts = cnt[:num_segments]
            else:
                out = segment_sum_mxu(seg, flat, num_segments + 1,
                                      out_dtype=out_dtype, interpret=interp)
            out = out.astype(leaf.dtype)
        else:
            out = segment_minmax_mxu(kind, seg, flat, num_segments + 1,
                                     interpret=interp)
        out = out[:num_segments]
        outs.append(out.reshape((num_segments,) + leaf.shape[1:]))

    acc = jax.tree.unflatten(treedef, outs)
    if counts is None:
        counts = segment_sum_mxu(seg, valid.astype(jnp.int32)[:, None],
                                 num_segments + 1, out_dtype=jnp.int32,
                                 interpret=interp)[:num_segments, 0]
    return acc, counts


# ---------------------------------------------------------------------------
# shuffle_reduce: the shuffle+merge+Reduce hot path
# ---------------------------------------------------------------------------

class ShuffleReduced(NamedTuple):
    """Sorted+merged rows plus the per-affected-key reduction."""

    k2: jax.Array        # [N] sorted primary keys (invalid rows at tail)
    mk: jax.Array        # [N] co-sorted secondary keys
    values: Any          # pytree of [N, ...] gathered through perm
    live: jax.Array      # [N] bool: last writer per (k2, mk), not a tombstone
    perm: jax.Array      # [N] int32 sort permutation
    acc: Any             # pytree of [key_cap, ...] accumulated live values
    counts: jax.Array    # [key_cap] int32 live rows per affected key


_INT32_MAX = 2**31 - 1


def _route(k2: jax.Array, affected_keys: jax.Array):
    """Each row's slot among ``affected_keys``, and whether its key is one.

    ``k2`` ascends, so each affected key owns the run [start, end) of rows
    equal to it.  A row's slot, ``searchsorted(affected_keys, k2)``, is the
    number of runs that end at or before it, and its key is affected where
    more runs have started than ended: key_cap searches into the rows and
    one prefix sum over them, O(n + key_cap log n), where a search per row
    costs O(n log key_cap).  An end at n, past the last row, is dropped.
    """
    n = k2.shape[0]
    starts = jnp.searchsorted(k2, affected_keys, side="left")
    ends = jnp.searchsorted(k2, affected_keys, side="right")
    marks = (jnp.zeros((2, n), jnp.int32)
             .at[0, starts].add(1, mode="drop")
             .at[1, ends].add(1, mode="drop"))
    started, local = jnp.cumsum(marks, axis=1)
    return local, started > local


def shuffle_reduce(reducer, k2: jax.Array, mk: jax.Array, values: Any,
                   valid: jax.Array, sign: jax.Array,
                   affected_keys: jax.Array, *,
                   backend: Optional[str] = None) -> ShuffleReduced:
    """Shuffle-sort, last-writer-wins merge, and reduce in one call.

    The engine's whole merge hot path: rows are sorted stably by (k2, mk)
    (invalid rows masked to the tail), the last row of each (k2, mk) run
    survives if its sign is positive (tombstones delete), and the live
    rows' values are reduced into the slots of ``affected_keys`` (sorted
    ascending, unique, padded with int32 max; ``counts`` counts live rows
    per slot, mean division stays with ``finalize_reduce``).  Both
    backends run the same composition (:func:`sort_pairs`, then
    :func:`segment_reduce`), so the xla path is the bitwise reference.

    Of ``valid`` and ``sign`` the merge reads one bit each, ``valid`` and
    ``sign > 0``: they travel through the sort as its ``flags``, and only
    ``values`` is gathered through the permutation.

    The route to the slots (:func:`_route`) requires the sorted ``k2`` to
    ascend, which :func:`sort_pairs` guarantees on both backends.
    """
    bk = resolve_backend(backend)
    n = k2.shape[0]
    key_cap = affected_keys.shape[0]
    # each stage is a named scope, so that a profile names the device time
    # of its operations (the scope path is their op_name metadata)
    with jax.named_scope("shuffle_reduce"):
        with jax.named_scope("sort"):
            k2m = jnp.where(valid, k2, jnp.int32(_INT32_MAX))
            res = sort_pairs(k2m, mk, values, num_keys=2,
                             flags=(valid, sign > 0), backend=bk)
        vals_s = res.payload
        valid_s, positive_s = res.flags

        # last-writer-wins per (k2, mk); tombstones delete
        with jax.named_scope("last_writer"):
            nk2 = jnp.roll(res.k2, -1)
            nmk = jnp.roll(res.mk, -1)
            is_last = jnp.logical_or(
                jnp.arange(n) == n - 1,
                jnp.logical_or(nk2 != res.k2, nmk != res.mk))
            live = valid_s & is_last & positive_s

        # route each live row to its affected-key slot
        with jax.named_scope("route"):
            local, in_set = _route(res.k2, affected_keys)
        with jax.named_scope("reduce"):
            acc, counts = segment_reduce(reducer, local, vals_s,
                                         live & in_set, key_cap, backend=bk)
    return ShuffleReduced(res.k2, res.mk, vals_s, live, res.perm, acc,
                          counts)


# ---------------------------------------------------------------------------
# group_reduce: the dql lowering shim
# ---------------------------------------------------------------------------

def group_reduce(reducer, keys: jax.Array, values: Any, valid: jax.Array,
                 num_groups: int, backend: Optional[str] = None):
    """Grouped reduce over a dense group-id space (``repro.dql`` lowering).

    Same contract as :func:`segment_reduce` — returns
    ``(accumulated pytree [num_groups, ...], counts [num_groups] int32)`` —
    but accepts the delta algebra's emission convention directly: negative
    or out-of-range keys mask the row (the idiom fused group_by chains use
    for padded fanout slots), composing with ``valid``.
    """
    keys = jnp.asarray(keys, jnp.int32)
    live = jnp.asarray(valid, jnp.bool_) & (keys >= 0) & (keys < num_groups)
    return segment_reduce(reducer, keys, values, live, num_groups,
                          backend=backend)

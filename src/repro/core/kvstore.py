"""Fixed-width key-value pair arrays: the TPU-native representation of MapReduce records.

Hadoop streams variable-length records off disk; a TPU wants dense, statically
shaped arrays resident in HBM.  We therefore represent a batch of kv-pairs as a
``KV`` pytree of arrays with an explicit validity mask (padding), and the
MRBGraph intermediate edges as an ``Edges`` pytree carrying (K2, MK, V2) per
the paper's Section 3.2.

Keys are int32 ids.  Invalid/padding entries carry key == INVALID_KEY so that a
lexicographic sort pushes them to the end of the buffer.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import to_host
from repro.kernels import ops

INVALID_KEY = jnp.int32(2**31 - 1)
_HASH_MULT = np.uint32(2654435761)


class KV(NamedTuple):
    """A batch of kv-pairs.  ``values`` may be any pytree of [N, ...] arrays."""

    keys: jax.Array          # [N] int32
    values: Any              # pytree of [N, ...]
    valid: jax.Array         # [N] bool

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    def count(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32))


class Edges(NamedTuple):
    """MRBGraph edges: fine-grain intermediate state (K2, MK, V2).

    ``sign`` distinguishes insertions (+1) from deletion tombstones (-1) in a
    *delta* MRBGraph; a preserved MRBGraph has sign == +1 everywhere.
    """

    k2: jax.Array            # [E] int32  destination Reduce instance
    mk: jax.Array            # [E] int32  globally unique Map instance key
    v2: Any                  # pytree of [E, ...] edge values
    valid: jax.Array         # [E] bool
    sign: jax.Array          # [E] int8   +1 insert, -1 delete

    @property
    def capacity(self) -> int:
        return self.k2.shape[0]

    def count(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32))


def make_kv(keys, values, valid=None) -> KV:
    keys = jnp.asarray(keys, jnp.int32)
    if valid is None:
        valid = jnp.ones(keys.shape[0], jnp.bool_)
    values = jax.tree.map(jnp.asarray, values)
    return KV(keys, values, jnp.asarray(valid, jnp.bool_))


def make_edges(k2, mk, v2, valid=None, sign=None) -> Edges:
    k2 = jnp.asarray(k2, jnp.int32)
    mk = jnp.asarray(mk, jnp.int32)
    if valid is None:
        valid = jnp.ones(k2.shape[0], jnp.bool_)
    if sign is None:
        sign = jnp.ones(k2.shape[0], jnp.int8)
    v2 = jax.tree.map(jnp.asarray, v2)
    return Edges(k2, mk, v2, jnp.asarray(valid, jnp.bool_),
                 jnp.asarray(sign, jnp.int8))


def hash32(keys: jax.Array, buckets: int) -> jax.Array:
    """Knuth multiplicative hash onto ``buckets`` partitions (uint32 domain)."""
    h = (keys.astype(jnp.uint32) * _HASH_MULT) >> jnp.uint32(16)
    return (h % jnp.uint32(buckets)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Sorting (the TPU analogue of Hadoop's shuffle-sort) — thin wrappers over
# the backend dispatcher in repro.kernels.ops
# ---------------------------------------------------------------------------

def sort_edges(edges: Edges, *, num_keys: int = 2,
               backend: Optional[str] = None) -> Edges:
    """Lexicographic stable sort of edges by (k2[, mk]).

    Invalid edges get k2 = INVALID_KEY so they land at the tail.  This mirrors
    the MapReduce shuffle: intermediate kv-pairs arrive at a Reduce task sorted
    by K2 (Section 3.3), and within a chunk by MK so that merge-joins are
    sequential.
    """
    k2 = jnp.where(edges.valid, edges.k2, INVALID_KEY)
    res = ops.sort_pairs(k2, edges.mk, (edges.v2, edges.valid, edges.sign),
                         num_keys=num_keys, backend=backend)
    v2, valid, sign = res.payload
    return Edges(res.k2, res.mk, v2, valid, sign)


def sort_kv(kv: KV, *, backend: Optional[str] = None) -> KV:
    keys = jnp.where(kv.valid, kv.keys, INVALID_KEY)
    res = ops.sort_pairs(keys, None, (kv.values, kv.valid), num_keys=1,
                         backend=backend)
    values, valid = res.payload
    return KV(res.k2, values, valid)


# ---------------------------------------------------------------------------
# Reducers (the Reduce function, expressed as a segment monoid)
# ---------------------------------------------------------------------------

class Reducer(NamedTuple):
    """Associative Reduce functions as segment monoids.

    All of the paper's applications (sum for PageRank/GIM-V/WordCount/APriori,
    min for SSSP, mean for Kmeans) are monoids, which is what makes both the
    MXU-friendly segment reduction and the accumulator-Reduce optimization of
    Section 3.5 applicable.

    ``invertible`` marks monoids that are abelian groups (sum): deletions can
    then be applied as inverse contributions *without* consulting the
    MRBGraph.  This generalizes the paper's accumulator optimization (which
    requires insert-only deltas) and is used as a beyond-paper fast path.
    """

    kind: str                                 # 'sum' | 'min' | 'max' | 'mean'
    finalize: Optional[Callable] = None       # (key, acc, count) -> value
    invertible: bool = False

    def identity_like(self, v2_leaf: jax.Array) -> jax.Array:
        if self.kind in ("sum", "mean"):
            return jnp.zeros_like(v2_leaf)
        if self.kind == "min":
            return jnp.full_like(v2_leaf, _type_max(v2_leaf.dtype))
        if self.kind == "max":
            return jnp.full_like(v2_leaf, _type_min(v2_leaf.dtype))
        raise ValueError(self.kind)


def _type_max(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).max
    return jnp.iinfo(dtype).max


def _type_min(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).min
    return jnp.iinfo(dtype).min


def sum_reducer(finalize=None) -> Reducer:
    return Reducer("sum", finalize, invertible=True)


def min_reducer(finalize=None) -> Reducer:
    return Reducer("min", finalize)


def max_reducer(finalize=None) -> Reducer:
    return Reducer("max", finalize)


def mean_reducer(finalize=None) -> Reducer:
    return Reducer("mean", finalize)


def segment_reduce(reducer: Reducer, segment_ids: jax.Array, values: Any,
                   valid: jax.Array, num_segments: int,
                   indices_are_sorted: bool = False,
                   backend: Optional[str] = None):
    """Reduce ``values`` into ``num_segments`` groups.

    Thin wrapper over the backend dispatcher (:mod:`repro.kernels.ops`).
    Returns (accumulated values pytree [K, ...], counts [K] int32).
    Invalid rows are routed to a scratch segment (index ``num_segments``)
    so they never pollute real groups.
    """
    return ops.segment_reduce(reducer, segment_ids, values, valid,
                              num_segments,
                              indices_are_sorted=indices_are_sorted,
                              backend=backend)


def finalize_reduce(reducer: Reducer, keys: jax.Array, acc: Any,
                    counts: jax.Array):
    """Apply mean division and the user finalize hook."""
    if reducer.kind == "mean":
        denom = jnp.maximum(counts, 1)
        acc = jax.tree.map(
            lambda a: a / denom.reshape((-1,) + (1,) * (a.ndim - 1)).astype(a.dtype),
            acc)
    if reducer.finalize is not None:
        acc = reducer.finalize(keys, acc, counts)
    return acc


# ---------------------------------------------------------------------------
# Compaction: gather the valid prefix of a padded buffer (bucketed capacity)
# ---------------------------------------------------------------------------

def next_bucket(n: int, minimum: int = 256) -> int:
    """Round up to the next power-of-two capacity bucket.

    Bucketing bounds the number of distinct shapes (hence XLA recompiles) to
    log2(N) while letting incremental work scale with the true delta size --
    the JAX replacement for Hadoop's dynamically sized spill files.
    """
    n = max(int(n), 1)
    b = minimum
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnums=(1,))
def compact_edges(edges: Edges, capacity: int) -> Edges:
    """Gather valid edges to the front of a ``capacity``-sized buffer."""
    order = jnp.argsort(~edges.valid, stable=True)  # valid first
    n = order.shape[0]
    if capacity > n:
        order = jnp.concatenate(
            [order, jnp.zeros(capacity - n, order.dtype)])
    take = order[:capacity]

    def g(leaf):
        return jnp.take(leaf, take, axis=0)

    n_valid = jnp.sum(edges.valid.astype(jnp.int32))
    new_valid = jnp.arange(capacity, dtype=jnp.int32) < n_valid
    return Edges(
        jnp.where(new_valid, g(edges.k2), INVALID_KEY),
        jnp.where(new_valid, g(edges.mk), INVALID_KEY),
        jax.tree.map(g, edges.v2),
        new_valid,
        jnp.where(new_valid, g(edges.sign), jnp.int8(0)),
    )


def edges_to_host(edges: Edges, *, sorted_valid_first: bool = False) -> dict:
    """Pull valid edges to host numpy (index maintenance lives host-side,
    exactly as Hadoop's chunk index lives outside the task JVM heap).

    ``sorted_valid_first=True`` (post-``sort_edges`` buffers): slice the
    valid prefix *on device* before the host transfer, so PCIe traffic is
    O(valid) instead of O(capacity) — sparse-emission Maps (e.g. APriori's
    presence tests) often fill <10% of their static edge buffer.
    """
    if sorted_valid_first:
        nvalid = int(to_host(jnp.sum(edges.valid)))
        cap = min(edges.capacity, next_bucket(max(nvalid, 1), 64))
        sl = lambda a: a[:cap]
        edges = Edges(sl(edges.k2), sl(edges.mk),
                      jax.tree.map(sl, edges.v2), sl(edges.valid),
                      sl(edges.sign))
    valid = to_host(edges.valid)
    idx = np.nonzero(valid)[0]
    return {
        "k2": to_host(edges.k2)[idx],
        "mk": to_host(edges.mk)[idx],
        "v2": jax.tree.map(lambda l: to_host(l)[idx], edges.v2),
        "sign": to_host(edges.sign)[idx],
    }

"""Batched cross-tenant refresh: many tenants' deltas, one kernel launch.

A fleet of small tenants makes the per-tenant refresh path dispatch-bound:
every micro-batch pays its own delta-Map launch, shuffle sort, and
segment reduce even when the delta holds a handful of rows.  This module
stacks compatible tenants' prepared deltas into one ``[T, cap]`` batch
and drives the union through a *single* pass of the existing engine:

1. one jitted, vmapped delta-Map over the tenant lane;
2. a **tenant-id lane** on K2 — each tenant's keys are offset by
   ``tenant * num_keys``, so the per-tenant key spaces become disjoint
   ranges of one global key space and one shuffle sort / segment reduce
   serves everyone;
3. one bucketed :func:`~repro.core.incremental._combine_edges` +
   :func:`~repro.core.incremental._merge_reduce` launch (the same
   ``ops.shuffle_reduce`` path and the
   same power-of-two bucket ladder, so executables are shared with the
   solo path's cache discipline);
4. a host-side split of the merged chunks and reduced values back to each
   tenant's MRBG store and result view.

Steady-state cost becomes launches-per-*batch* instead of
launches-per-*tenant*.  Per-tenant outputs are bit-for-bit identical to a
solo refresh: the key ranges are disjoint, the shuffle sort is stable,
and within every (k2, mk) segment the row order (preserved rows before
delta rows, emission order within each) matches what the tenant's own
refresh would have fed the reducer.
"""
from __future__ import annotations

import functools
import time
from contextlib import ExitStack
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.incremental import (DeltaKV, _combine_edges, _merge_reduce,
                                    _v2_dict, pad_delta)
from repro.core.kvstore import KV, Edges, edges_to_host, next_bucket, sort_edges
from repro.kernels import jitcache, ops

MAX_GLOBAL_KEY = 2**31 - 1


def batch_signature(ss, prep) -> Optional[tuple]:
    """Group key for tenants whose prepared refreshes can share a launch;
    ``None`` when the tenant must refresh solo.

    Only ``onestep-mrbg`` drivers with an ``update`` decision batch — the
    iterative, accumulator, and distributed paths (and rerun/auto-off
    decisions) keep the per-tenant path.  Two tenants share a signature
    when they run the same Map *function object*, the same reducer, key
    count, and resolved backend, and emit identical delta value schemas —
    exactly the conditions under which one trace serves both.
    """
    drv = ss.session._driver
    if getattr(drv, "kind", None) != "onestep-mrbg":
        return None
    if prep.decision is None or prep.decision.action != "update":
        return None
    spec = ss.session.spec
    delta = prep.res.delta
    leaves = tuple(sorted(
        (name, str(np.asarray(a).dtype), tuple(np.asarray(a).shape[1:]))
        for name, a in _v2_dict(delta.values).items()))
    return (id(spec.map_fn), spec.reducer, spec.num_keys,
            ops.resolve_backend(ss.session.config.backend), leaves)


@functools.partial(jax.jit, static_argnums=(0,))
def _batched_delta_map(spec_static, delta: DeltaKV) -> Edges:
    """vmapped delta Map over ``[T, cap]`` stacked tenants, tenant-id K2
    offset, then ONE shuffle sort over the flattened union."""
    jitcache.count_trace("serve._batched_delta_map")
    map_fn, num_keys, backend = spec_static

    def one_tenant(keys, values, valid, sign):
        return map_fn(KV(keys, values, valid), sign)

    edges = jax.vmap(one_tenant)(delta.keys, delta.values,
                                 delta.valid, delta.sign)
    t_idx = jnp.arange(edges.k2.shape[0], dtype=jnp.int32)[:, None]
    gk2 = jnp.where(edges.valid, edges.k2 + t_idx * num_keys, 0)
    flat = Edges(gk2.reshape(-1), edges.mk.reshape(-1),
                 jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                              edges.v2),
                 edges.valid.reshape(-1), edges.sign.reshape(-1))
    return sort_edges(flat, backend=backend)


def _stack_tenants(deltas: List[DeltaKV], cap: int, t_pad: int) -> DeltaKV:
    """Stack per-tenant deltas (row-padded to ``cap``) into ``[t_pad, cap]``
    lanes; padding tenants are all-invalid rows."""
    padded = [pad_delta(d, cap) for d in deltas]

    def lane(get):
        arrs = [np.asarray(get(d)) for d in padded]
        out = np.zeros((t_pad, cap) + arrs[0].shape[1:], arrs[0].dtype)
        for t, a in enumerate(arrs):
            out[t] = a
        return jnp.asarray(out)

    return DeltaKV(lane(lambda d: d.keys),
                   lane(lambda d: d.record_ids),
                   {n: lane(lambda d, n=n: d.values[n])
                    for n in padded[0].values},
                   lane(lambda d: d.valid),
                   lane(lambda d: d.sign))


def execute_group(items: List[Tuple[object, object]],
                  delta_bucket_min: int = 64) -> None:
    """Run one batched refresh for ``items`` — ``(handle, prep)`` pairs
    sharing a :func:`batch_signature` — and commit every participant.

    On any failure every participant's mirror is rolled back and the
    exception re-raised; no tenant is left half-refreshed.  Each tenant's
    scheduler observes its *share* of the batch wall-clock, so the EWMA
    cost model learns the amortized batched cost.
    """
    t0 = time.perf_counter()
    gen0 = jitcache.generation()
    with ExitStack() as stack:
        for h, _ in items:
            stack.enter_context(h.ss._lock)
        try:
            _run(items, delta_bucket_min)
        except BaseException:
            for h, prep in items:
                h.ss.rollback_batch(prep)
            raise
        wall = time.perf_counter() - t0
        retraced = jitcache.generation() != gen0
        share = wall / len(items)
        for h, prep in items:
            h.ss.session.absorb_refresh(share)
            h.ss.commit_batch(prep, "update", share, retraced)


def _run(items, delta_bucket_min: int) -> None:
    session0 = items[0][0].ss.session
    spec = session0.spec
    num_keys = spec.num_keys
    backend = ops.resolve_backend(session0.config.backend)
    reducer = spec.reducer

    t_pad = next_bucket(len(items), 1)
    if t_pad * num_keys > MAX_GLOBAL_KEY:
        raise ValueError(
            f"tenant-id lane overflow: {t_pad} tenants x {num_keys} keys "
            f"exceeds int32; lower ServeTier(max_batch_tenants=...)")
    cap = next_bucket(max(p.res.delta.capacity for _, p in items),
                      delta_bucket_min)
    stacked = _stack_tenants([p.res.delta for _, p in items], cap, t_pad)

    # 1-2) one vmapped delta Map + one shuffle sort for the whole group
    edges = _batched_delta_map((spec.map_fn, num_keys, backend), stacked)
    dh = edges_to_host(edges, sorted_valid_first=True)
    affected_g = np.unique(dh["k2"])        # global (tenant-offset) keys
    for h, _ in items:
        for store in h.ss.session.stores:
            store.reset_stats()
    if affected_g.size == 0:
        for h, _ in items:
            h.ss.session._driver._affected = 0
        return

    # 3) per-tenant store queries, re-offset into the global key space;
    # concatenated tenant-major so preserved rows precede delta rows and
    # the stable shuffle sort keeps solo-identical segment order
    owner = affected_g // num_keys
    dv2 = _v2_dict(dh["v2"])
    pk_parts, pmk_parts = [], []
    pv_parts = {n: [] for n in dv2}
    for t, (h, _) in enumerate(items):
        mask = owner == t
        local = (affected_g[mask] - t * num_keys).astype(affected_g.dtype)
        pk2, pmk, pv2, _plen = h.ss.session.store.query(local)
        if pv2 is None or pk2.shape[0] == 0:
            continue
        pk_parts.append(pk2.astype(np.int64) + t * num_keys)
        pmk_parts.append(pmk)
        for n, a in _v2_dict(pv2).items():
            pv_parts[n].append(a)
    if pk_parts:
        pk2_all = np.concatenate(pk_parts).astype(np.int32)
        pmk_all = np.concatenate(pmk_parts)
        pv2_all = {n: np.concatenate(parts) for n, parts in pv_parts.items()}
    else:
        pk2_all = np.zeros(0, np.int32)
        pmk_all = np.zeros(0, np.int32)
        pv2_all = {n: np.zeros((0,) + a.shape[1:], a.dtype)
                   for n, a in dv2.items()}

    # 4-5) ONE bucketed merge + segment reduce over the union
    key_cap = next_bucket(affected_g.size, 64)
    combined = _combine_edges(pk2_all, pmk_all, pv2_all,
                              dh["k2"], dh["mk"], dv2,
                              np.asarray(dh["sign"], np.int8))
    keys_pad = np.full(key_cap, np.int32(MAX_GLOBAL_KEY), np.int32)
    keys_pad[:affected_g.size] = affected_g.astype(np.int32)
    merged, values, counts = _merge_reduce(reducer, key_cap, backend,
                                           combined, jnp.asarray(keys_pad))

    # 6) split the merged chunks / reduced values back per tenant
    mh = edges_to_host(merged)
    m_owner = mh["k2"] // num_keys
    m_local = (mh["k2"] % num_keys).astype(mh["k2"].dtype)
    mv2 = _v2_dict(mh["v2"])
    counts_h = np.asarray(counts)[:affected_g.size]
    vals_h = {n: np.asarray(a)[:affected_g.size]
              for n, a in _v2_dict(values).items()}
    for t, (h, _) in enumerate(items):
        drv = h.ss.session._driver
        sel = m_owner == t
        drv.store.append(m_local[sel], mh["mk"][sel],
                         {n: a[sel] for n, a in mv2.items()})
        amask = owner == t
        local = (affected_g[amask] - t * num_keys).astype(affected_g.dtype)
        c_t = counts_h[amask]
        drv.store.mark_deleted(local[c_t == 0])
        drv.view.patch(local, {n: a[amask] for n, a in vals_h.items()}, c_t)
        drv._affected = int(amask.sum())
        drv._counts = drv.view.counts
        drv.mode = "incremental"

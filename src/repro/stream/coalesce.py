"""Micro-batch coalescer: cancel opposing +/- rows before the engine runs.

A streaming producer that updates record r three times in one micro-batch
emits six rows ('-' old, '+' new, three times); the engine only needs two —
a tombstone for the value the preserved MRBGraph was computed from, and an
insert of the newest value.  Per record id the net effect of an in-order
signed row sequence is fully determined by its first and last rows:

  first '-' , last '+'   ->  keep both   (update: tombstone old, insert new)
  first '-' , last '-'   ->  keep first  (net delete)
  first '+' , last '+'   ->  keep last   (net insert)
  first '+' , last '-'   ->  keep none   (created and destroyed in-batch)

The hot path is pure JAX riding the PR-3 backend dispatcher: a stable
lexicographic sort by (record id, arrival index) through
:func:`repro.kernels.ops.sort_pairs` groups each record's rows while
preserving arrival order, and a segment-sum of the signs through
:func:`repro.kernels.ops.segment_reduce` yields each record's net row
balance (the upsert/delete telemetry).  Only the final variable-length
compaction of surviving rows happens on the host — the same host/device
split as the incremental engine itself.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.incremental import DeltaKV, make_delta
from repro.core.kvstore import INVALID_KEY, next_bucket
from repro.core.spans import to_device, to_host
from repro.kernels import jitcache, ops


class CoalesceResult(NamedTuple):
    delta: Optional[DeltaKV]   # None when every row cancelled out
    n_in: int                  # rows entering the coalescer
    n_out: int                 # rows surviving (== delta rows)
    n_records: int             # distinct record ids touched
    n_inserts: int             # records whose net effect is an insert
    n_deletes: int             # records whose net effect is a delete

    @property
    def n_cancelled(self) -> int:
        return self.n_in - self.n_out


@functools.partial(jax.jit, static_argnums=(0, 1))
def _coalesce_kernel(cap: int, backend: Optional[str], rid: jax.Array,
                     sign: jax.Array, valid: jax.Array):
    """Device part: sort + group-boundary flags + per-record net sign."""
    jitcache.count_trace("stream._coalesce_kernel")
    iota = jnp.arange(cap, dtype=jnp.int32)
    rid_m = jnp.where(valid, rid, INVALID_KEY)
    srt = ops.sort_pairs(rid_m, iota, payload=(sign, valid), num_keys=2,
                         backend=backend)
    sg, v = srt.payload
    k2 = srt.k2
    first = jnp.logical_or(iota == 0, k2 != jnp.roll(k2, 1))
    last = jnp.logical_or(iota == cap - 1, k2 != jnp.roll(k2, -1))
    keep = v & ((first & (sg < 0)) | (last & (sg > 0)))
    # net row balance per record: +1 net insert, -1 net delete, 0 update
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    net, cnt = ops.segment_reduce("sum", seg, sg.astype(jnp.int32), v, cap,
                                  backend=backend)
    return srt.perm, keep, first & v, net, cnt


def coalesce_rows(record_ids: np.ndarray, values: Dict[str, np.ndarray],
                  sign: np.ndarray, *,
                  backend: Optional[str] = None) -> CoalesceResult:
    """Coalesce one micro-batch of signed rows (arrival order) into the
    minimal equivalent :class:`DeltaKV`."""
    record_ids = np.asarray(record_ids, np.int32)
    sign = np.asarray(sign, np.int8)
    n = int(record_ids.shape[0])
    if n == 0:
        return CoalesceResult(None, 0, 0, 0, 0, 0)
    bk = ops.resolve_backend(backend)
    cap = next_bucket(n, 64)
    rid_pad = np.full(cap, np.int32(2**31 - 1), np.int32)
    rid_pad[:n] = record_ids
    sg_pad = np.zeros(cap, np.int8)
    sg_pad[:n] = sign
    valid = np.zeros(cap, bool)
    valid[:n] = True

    perm, keep, firsts, net, cnt = _coalesce_kernel(
        cap, bk, to_device(rid_pad), to_device(sg_pad), to_device(valid))
    perm = to_host(perm)
    keep = to_host(keep)
    firsts = to_host(firsts)
    net = to_host(net)
    cnt = to_host(cnt)

    # host compaction: surviving rows in (record id, arrival) order
    sel = perm[keep]
    n_records = int(firsts.sum())
    real = cnt > 0                      # segments holding valid rows
    n_inserts = int(((net > 0) & real).sum())
    n_deletes = int(((net < 0) & real).sum())
    if sel.size == 0:
        return CoalesceResult(None, n, 0, n_records, n_inserts, n_deletes)
    delta = make_delta(record_ids[sel],
                       {nm: np.asarray(a)[sel] for nm, a in values.items()},
                       sign[sel])
    return CoalesceResult(delta, n, int(sel.size), n_records, n_inserts,
                          n_deletes)


def concat_records(records: Sequence[Any]):
    """Concatenate DeltaRecords (arrival order) into flat row arrays."""
    rids = np.concatenate([np.asarray(r.record_ids, np.int32)
                           for r in records])
    signs = np.concatenate([np.asarray(r.sign, np.int8) for r in records])
    names = records[0].values.keys()
    values = {n: np.concatenate([np.asarray(r.values[n]) for r in records])
              for n in names}
    return rids, values, signs


def coalesce(records: Sequence[Any], *,
             backend: Optional[str] = None) -> CoalesceResult:
    """Coalesce a sequence of :class:`repro.stream.DeltaRecord`s."""
    if not records:
        return CoalesceResult(None, 0, 0, 0, 0, 0)
    rids, values, signs = concat_records(records)
    return coalesce_rows(rids, values, signs, backend=backend)

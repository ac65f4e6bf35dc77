"""Fine-grain incremental one-step processing (paper Section 3.3).

Pipeline for a delta input ΔD against a preserved job A:

  1. *Incremental Map*: invoke the Map function only on the changed records;
     edges emitted by '-' records become tombstones (sign = -1).
  2. *Shuffle*: sort the delta MRBGraph by (K2, MK).
  3. *State retrieval*: the affected K2 set is queried against the MRBG-Store
     (host side, read-window policies of Section 3.4/5.2).
  4. *Merge*: preserved chunks + delta edges are joined with a stable sort;
     for each (K2, MK) the **last** version wins and tombstones delete
     (an update arrives as '-' then '+', exactly as in the paper).
  5. *Incremental Reduce*: segment-reduce only the affected K2 groups and
     patch the dense result view.
  6. *State preservation*: merged chunks are appended to the MRBG-Store and
     the chunk index repointed (obsolete chunks compacted offline).

Everything on-device is jitted with power-of-two bucketed capacities so that
the work (and the number of distinct XLA programs) scales with |Δ|, not |D|.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import JobSpec, run_onestep
from repro.core.kvstore import (
    INVALID_KEY, KV, Edges, Reducer, edges_to_host, finalize_reduce, make_kv,
    next_bucket, sort_edges,
)
from repro.core.mrbg_store import MRBGStore
from repro.core.spans import span, to_device, to_host
from repro.kernels import jitcache, ops


class DeltaKV(NamedTuple):
    """A delta input: kv-pairs marked '+' (insert) or '-' (delete).

    An update is encoded as a deletion followed by an insertion of the same
    key (paper Section 3.1); both rows carry the same record id so the
    replayed Map instance overwrites its previous edges.
    """

    keys: jax.Array          # [N] int32 (K1; semantic only, not used by engine)
    record_ids: jax.Array    # [N] int32 Map-instance identity (drives MK)
    values: Any              # pytree of [N, ...]
    valid: jax.Array         # [N] bool
    sign: jax.Array          # [N] int8 (+1 / -1)

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]


def make_delta(record_ids, values, sign, *, keys=None,
               valid=None) -> DeltaKV:
    """Build a :class:`DeltaKV`.

    ``keys`` (the semantic K1) defaults to ``record_ids`` — for every engine
    app the Map-instance identity *is* the record key, so the historical
    ``make_delta(rid, rid, ...)`` spelling is no longer needed (and the
    pre-``repro.api`` positional order is no longer accepted: ``keys`` and
    ``valid`` are keyword-only).
    """
    record_ids = to_device(record_ids, jnp.int32)
    if keys is None:
        keys = record_ids
    keys = to_device(keys, jnp.int32)
    if valid is None:
        valid = jnp.ones(keys.shape[0], jnp.bool_)
    return DeltaKV(keys, record_ids,
                   jax.tree.map(to_device, values),
                   to_device(valid, jnp.bool_), to_device(sign, jnp.int8))


def pad_delta(delta: DeltaKV, capacity: int) -> DeltaKV:
    """Pad a delta to a bucketed row capacity (padding rows are invalid).

    Every consumer of a :class:`DeltaKV` masks on ``valid``, so padding is
    semantically inert; what it buys is *shape discipline*: deltas whose
    row counts land in the same bucket share one traced/compiled refresh
    program instead of retracing per distinct row count.
    """
    n = delta.capacity
    if capacity < n:
        raise ValueError(f"pad_delta capacity {capacity} < delta rows {n}")
    if capacity == n:
        return delta

    def ext(a):
        pad = jnp.zeros((capacity - n,) + a.shape[1:], a.dtype)
        return jnp.concatenate([a, pad])

    return DeltaKV(ext(delta.keys), ext(delta.record_ids),
                   jax.tree.map(ext, delta.values),
                   ext(delta.valid), ext(delta.sign))


def apply_delta_host(keys: np.ndarray, values: Dict[str, np.ndarray],
                     valid: np.ndarray, delta: DeltaKV) -> None:
    """Apply a signed delta to a host-side record mirror, in place.

    The mirror plays the role of the partitioned input file on HDFS: '-'
    rows invalidate a record slot, '+' rows (re)write it.
    """
    rid = to_host(delta.record_ids)
    sgn = to_host(delta.sign)
    dvalid = to_host(delta.valid)
    dkeys = to_host(delta.keys)
    dvals = {n: to_host(delta.values[n]) for n in values}
    for i in np.nonzero(dvalid)[0]:
        r = int(rid[i])
        if sgn[i] < 0:
            valid[r] = False
        else:
            valid[r] = True
            keys[r] = int(dkeys[i])
            for n, a in values.items():
                a[r] = dvals[n][i]


class ResultView:
    """Host-side dense view of the job's current output <K3,V3> (K3 == K2).

    Plays the role of the job's output file on HDFS: incremental runs patch
    only the affected keys.
    """

    def __init__(self, num_keys: int, values: Dict[str, np.ndarray],
                 valid: np.ndarray, counts: np.ndarray):
        self.num_keys = num_keys
        self.values = values
        self.valid = valid
        self.counts = counts

    @classmethod
    def from_job(cls, num_keys: int, results, counts) -> "ResultView":
        values = {n: np.array(a) for n, a in results.values.items()}
        return cls(num_keys, values, np.array(results.valid),
                   np.array(counts))

    def patch(self, keys: np.ndarray, values: Dict[str, np.ndarray],
              counts: np.ndarray) -> None:
        keys = np.asarray(keys)
        sel = keys < self.num_keys
        k = keys[sel]
        for name, arr in values.items():
            self.values[name][k] = np.asarray(arr)[sel]
        self.counts[k] = np.asarray(counts)[sel]
        self.valid[k] = self.counts[k] > 0

    def as_dict(self) -> Dict[str, np.ndarray]:
        return {n: np.where(
            self.valid.reshape((-1,) + (1,) * (a.ndim - 1)), a, 0)
            for n, a in self.values.items()}


class IncrementalJob:
    """Owns the preserved MRBGraph + result view of one MapReduce job."""

    def __init__(self, spec: JobSpec, value_bytes: int = 8,
                 policy: str = "multi-dynamic-window",
                 backend: Optional[str] = None):
        self.spec = spec
        self.backend = backend
        self.store = MRBGStore(spec.num_keys, value_bytes, policy=policy)
        self.view: Optional[ResultView] = None

    # -- initial run -------------------------------------------------------
    def initial_run(self, inp: KV) -> ResultView:
        res = run_onestep(self.spec, inp, preserve=True,
                          backend=self.backend)
        host = edges_to_host(res.edges)
        self.store.append(host["k2"], host["mk"], _v2_dict(host["v2"]))
        self.view = ResultView.from_job(self.spec.num_keys, res.results,
                                        res.counts)
        return self.view

    # -- incremental run ---------------------------------------------------
    def incremental_run(self, delta: DeltaKV) -> ResultView:
        assert self.view is not None, "initial_run first"
        stats = incremental_onestep(self.spec, delta, self.store, self.view,
                                    backend=self.backend)
        return self.view

    def refresh_stats(self) -> Dict[str, Any]:
        return {"store_batches": self.store.n_batches,
                "store_bytes": self.store.file_bytes(),
                "live_bytes": self.store.live_bytes(),
                "io": self.store.stats}


def _v2_dict(v2) -> Dict[str, np.ndarray]:
    if isinstance(v2, dict):
        return v2
    return {"v": v2}


def _v2_tree(v2_dict, template):
    if isinstance(template, dict):
        return v2_dict
    return v2_dict["v"]


# ---------------------------------------------------------------------------
# The jitted incremental kernel: delta map -> merge -> incremental reduce
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0,))
def _delta_map(spec_static, delta: DeltaKV) -> Edges:
    jitcache.count_trace("incremental._delta_map")
    map_fn, backend = spec_static
    kv = KV(delta.keys, delta.values, delta.valid)
    edges = map_fn(kv, delta.sign)
    return sort_edges(edges, backend=backend)


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3,))
def _merge_reduce(reducer: Reducer, key_cap: int, backend: Optional[str],
                  combined: Edges, affected_keys: jax.Array):
    """Join preserved chunks with delta edges; reduce affected groups.

    ``combined`` holds preserved rows first, then delta rows (so that the
    stable shuffle sort leaves equal-(k2,mk) delta rows *after* the
    preserved version and last-writer-wins overrides).  It is donated:
    the buffers are built fresh per refresh and the sorted merge aliases
    them in place instead of paying another O(capacity) copy.
    ``affected_keys`` is sorted ascending, padded with INVALID_KEY.
    Returns (merged edges [sorted, valid-masked], values pytree [key_cap],
    counts [key_cap]).
    """
    jitcache.count_trace("incremental._merge_reduce")
    # the whole sort -> last-writer-wins -> segment-reduce chain lives in
    # ops.shuffle_reduce
    sr = ops.shuffle_reduce(reducer, combined.k2, combined.mk, combined.v2,
                            combined.valid, combined.sign, affected_keys,
                            backend=backend)
    n = sr.k2.shape[0]
    merged = Edges(sr.k2, sr.mk, sr.values, sr.live, jnp.ones(n, jnp.int8))
    values = finalize_reduce(reducer, affected_keys, sr.acc, sr.counts)
    return merged, values, sr.counts


def incremental_onestep(spec: JobSpec, delta: DeltaKV, store: MRBGStore,
                        view: ResultView,
                        backend: Optional[str] = None) -> Dict[str, Any]:
    """One incremental refresh; patches ``view`` and ``store`` in place."""
    bk = ops.resolve_backend(backend)
    # 1-2) incremental Map + shuffle of the delta MRBGraph
    with span("repro.incremental.delta_map"):
        delta_edges = _delta_map((spec.map_fn, bk), delta)
        dh = edges_to_host(delta_edges, sorted_valid_first=True)

    # 3) affected keys, queried against the store in sorted order (the
    # feed, the host work that builds the merge's input, is timed on both
    # sides of the query)
    with span("repro.incremental.feed"):
        affected = np.unique(dh["k2"])
    if affected.size == 0:
        return {"affected": 0, "merged": 0}
    pk2, pmk, pv2, _plen = store.query(affected)

    # 4-5) pad to buckets and run the jitted merge+reduce
    with span("repro.incremental.feed"):
        if pv2 is None:
            pv2 = {n: np.zeros((0,) + a.shape[1:], a.dtype)
                   for n, a in _v2_dict(dh["v2"]).items()}
        key_cap = next_bucket(affected.size, 64)
        dsign = np.asarray(dh["sign"], np.int8)
        combined = _combine_edges(pk2, pmk, pv2, dh["k2"], dh["mk"],
                                  _v2_dict(dh["v2"]), dsign)
        keys_pad = np.full(key_cap, np.int32(2**31 - 1), np.int32)
        keys_pad[:affected.size] = affected.astype(np.int32)
        keys_dev = to_device(keys_pad)

    with span("repro.incremental.merge"):
        merged, values, counts = _merge_reduce(spec.reducer, key_cap, bk,
                                               combined, keys_dev)
        mh = edges_to_host(merged)

    # 6) preserve merged chunks + patch results
    store.append(mh["k2"], mh["mk"], _v2_dict(mh["v2"]))
    with span("repro.incremental.patch"):
        counts_h = to_host(counts)[:affected.size]
        vals_h = {n: to_host(a)[:affected.size]
                  for n, a in _v2_dict(values).items()}
        view.patch(affected, vals_h, counts_h)
    gone = affected[counts_h == 0]
    store.mark_deleted(gone)
    return {"affected": int(affected.size), "merged": int(mh["k2"].shape[0]),
            "deleted_keys": int(gone.size)}


def _pad_edges(k2: np.ndarray, mk: np.ndarray, v2: Dict[str, np.ndarray],
               sign: np.ndarray, cap: int) -> Edges:
    n = int(k2.shape[0])
    ik = np.int32(2**31 - 1)
    out_k2 = np.full(cap, ik, np.int32); out_k2[:n] = k2
    out_mk = np.full(cap, ik, np.int32); out_mk[:n] = mk
    out_sign = np.zeros(cap, np.int8); out_sign[:n] = sign
    valid = np.zeros(cap, bool); valid[:n] = True
    out_v2 = {}
    for name, a in v2.items():
        buf = np.zeros((cap,) + a.shape[1:], a.dtype)
        buf[:n] = a
        out_v2[name] = buf
    return Edges(jnp.asarray(out_k2), jnp.asarray(out_mk),
                 jax.tree.map(jnp.asarray, out_v2),
                 jnp.asarray(valid), jnp.asarray(out_sign))


def _combine_edges(pk2: np.ndarray, pmk: np.ndarray,
                   pv2: Dict[str, np.ndarray],
                   dk2: np.ndarray, dmk: np.ndarray,
                   dv2: Dict[str, np.ndarray], dsign: np.ndarray,
                   minimum: int = 64) -> Edges:
    """One bucketed host buffer: preserved rows first, then delta rows.

    Feeding :func:`_merge_reduce` a single pre-concatenated buffer (instead
    of two separately padded ones concatenated on device) keeps the shape
    space one-dimensional — one bucket per *total* edge count — and lets
    the jit donate the buffer to the in-place shuffle sort.
    """
    n_p, n_d = int(pk2.shape[0]), int(dk2.shape[0])
    cap = next_bucket(max(n_p + n_d, 1), minimum)
    ik = np.int32(2**31 - 1)
    out_k2 = np.full(cap, ik, np.int32)
    out_k2[:n_p] = pk2; out_k2[n_p:n_p + n_d] = dk2
    out_mk = np.full(cap, ik, np.int32)
    out_mk[:n_p] = pmk; out_mk[n_p:n_p + n_d] = dmk
    out_sign = np.zeros(cap, np.int8)
    out_sign[:n_p] = 1; out_sign[n_p:n_p + n_d] = dsign
    valid = np.zeros(cap, bool); valid[:n_p + n_d] = True
    out_v2 = {}
    for name, a in dv2.items():
        buf = np.zeros((cap,) + a.shape[1:], a.dtype)
        buf[:n_p] = pv2[name]; buf[n_p:n_p + n_d] = a
        out_v2[name] = buf
    return Edges(to_device(out_k2), to_device(out_mk),
                 jax.tree.map(to_device, out_v2),
                 to_device(valid), to_device(out_sign))

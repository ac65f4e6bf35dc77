"""Session: the single supported way to drive the i2MapReduce engine.

A job is declared once (a :class:`JobSpec` or :class:`IterSpec`) together
with one :class:`RunConfig`; the session then transparently routes

  * ``run(data)``     -> full one-step execution, or prime-loop convergence,
  * ``update(delta)`` -> fine-grain incremental refresh (§3.3), the
                         accumulator fast path (§3.5), incremental iterative
                         refresh with CPC + auto MRBG-off (§5), or a
                         distributed re-converge,
  * ``result`` / ``report()`` -> one uniform output surface,
  * ``checkpoint()`` / ``restore()`` -> fault tolerance (§6),

exactly as the paper presents i2MapReduce: one system, with the engine —
not the caller — choosing between incremental refresh, iterative
recomputation, and fallback re-computation.  Distributed execution is not a
different API: ``RunConfig(mesh=...)`` turns the same spec into the
shard_map + all_to_all engine of §4.3.

The historical entry points (``run_onestep``, ``IncrementalJob``,
``run_iterative``/``run_plain``, ``IncrIterJob``, ``run_distributed``,
``AccumulatorJob``, ``checkpoint_job``/``restore_job``) are the internal
implementation that the Session drives; they carry no API stability promise.
"""
from __future__ import annotations

import time
from pathlib import Path
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import RunConfig
from repro.api.report import RunReport, ShuffleStats
from repro.core.engine import JobSpec, run_onestep
from repro.core.incr_iter import IterationLog
from repro.core.incremental import (
    DeltaKV, ResultView, _v2_dict, apply_delta_host, incremental_onestep,
    pad_delta,
)
from repro.core.iterative import IterSpec, State, run_iterative, run_plain
from repro.core.kvstore import KV, edges_to_host, next_bucket
from repro.core.mrbg_store import IOStats, MRBGStore
from repro.core.spans import span, take
from repro.kernels import jitcache

Spec = Union[JobSpec, IterSpec]


class Session:
    """Owns one declared job and all of its preserved state across epochs."""

    def __init__(self, spec: Spec, config: Optional[RunConfig] = None):
        self.spec = spec
        self.config = config or RunConfig()
        if self.config.compilation_cache_dir is not None:
            jitcache.enable_persistent_cache(self.config.compilation_cache_dir)
        self.epoch = -1                     # becomes 0 on run()
        self._last: Optional[RunReport] = None
        # bounded RunReport history (oldest first) — the raw material for
        # online refresh-cost models (repro.stream.RefreshScheduler)
        self.history: list = []
        self._driver = self._make_driver()

    def _make_driver(self):
        spec, config = self.spec, self.config
        if isinstance(spec, JobSpec):
            if config.mesh is not None:
                return _DistOneStep(spec, config)
            path = config.onestep_path
            if path == "auto":
                path = ("accumulator" if spec.reducer.invertible else "mrbg")
            return (_OneStepAccumulator(spec, config)
                    if path == "accumulator" else _OneStepMRBG(spec, config))
        elif isinstance(spec, IterSpec):
            if config.mesh is not None:
                return _Distributed(spec, config)
            elif config.plain_shuffle:
                return _PlainIter(spec, config)
            return _IncrIter(spec, config)
        # deferred import: repro.dql lowers *to* this layer, so the api
        # package must not import it at module load
        from repro.dql.driver import _QueryDriver
        from repro.dql.lower import QuerySpec
        if isinstance(spec, QuerySpec):
            return _QueryDriver(spec, config)
        raise TypeError(f"spec must be JobSpec, IterSpec or QuerySpec, "
                        f"got {type(spec).__name__}")

    # -- lifecycle ---------------------------------------------------------
    def run(self, data: KV) -> RunReport:
        """Initial job: one-step run or iterative convergence."""
        if self.epoch >= 0:
            raise RuntimeError("run() already executed for this session; "
                               "apply changes with update(delta)")
        t0 = time.perf_counter()
        self._driver.run(data)
        self.epoch = 0
        return self._finish(t0)

    def update(self, delta: DeltaKV) -> RunReport:
        """Refresh the preserved job against a signed delta input."""
        if self.epoch < 0:
            raise RuntimeError("update() before run(); execute the initial "
                               "job first")
        t0 = time.perf_counter()
        with span("repro.session.update", epoch=self.epoch + 1):
            # bucket the delta's row capacity so the jitted refresh path
            # traces once per power-of-two bucket, not once per distinct
            # row count (multi-source query deltas arrive as {source:
            # DeltaKV}; the query driver buckets each encoded feed itself)
            if isinstance(delta, DeltaKV):
                cap = next_bucket(delta.capacity,
                                  self.config.delta_bucket_min)
                if cap != delta.capacity:
                    delta = pad_delta(delta, cap)
            self._driver.update(delta)
        self.epoch += 1
        return self._finish(t0)

    def rerun(self, data: KV) -> RunReport:
        """Full re-computation refresh: drop every preserved structure and
        recompute from scratch on the (fully updated) input, as one more
        epoch of this session.

        This is the scheduler's alternative to ``update(delta)`` once |Δ|
        grows past the paper's Fig. 8 crossover — the same decision the
        engine takes internally for iterative jobs (§5.2 MRBG-off), exposed
        at the session level so a serving layer can take it per micro-batch.
        """
        if self.epoch < 0:
            raise RuntimeError("rerun() before run(); execute the initial "
                               "job first")
        t0 = time.perf_counter()
        self._driver = self._make_driver()   # fresh preserved state
        self._driver.run(data)
        self.epoch += 1
        return self._finish(t0)

    def grow_records(self, capacity: int) -> None:
        """Extend the record-id address space to ``capacity`` rows.

        Streaming sources may insert brand-new record ids past the seed
        data's capacity; drivers that mirror the structure file
        (iterative / plain / distributed-iterative) extend their mirrors
        with invalid rows and rebuild derived indexes.  One-step drivers
        keep no per-record structure — record ids only feed the MK lane —
        so this is a no-op for them.  Shrinking is never performed.
        """
        hook = getattr(self._driver, "grow_records", None)
        if hook is not None:
            hook(int(capacity))

    def absorb_refresh(self, seconds: float) -> RunReport:
        """Account one refresh epoch executed *outside* ``update()``.

        The serving tier's batched cross-tenant refresh drives several
        sessions' preserved state through one shared kernel launch; each
        participant then calls this with its share of the batch wall-clock
        so ``epoch``/``history``/auto-checkpointing stay consistent with
        the per-tenant path.
        """
        if self.epoch < 0:
            raise RuntimeError("absorb_refresh() before run(); execute the "
                               "initial job first")
        self.epoch += 1
        return self._finish(time.perf_counter() - seconds)

    def _finish(self, t0: float) -> RunReport:
        # skip the dense result copy here: each epoch would otherwise pay
        # an O(|D|) device->host transfer even when nobody reads it
        rep = self.report(include_result=False)
        rep.seconds = time.perf_counter() - t0
        rep.spans, rep.counters = take()
        self._last = rep
        self.history.append(rep)
        if len(self.history) > self.config.report_history:
            del self.history[:-self.config.report_history]
        cfg = self.config
        if (cfg.checkpoint_dir is not None and cfg.checkpoint_every > 0
                and self.epoch % cfg.checkpoint_every == 0):
            self.checkpoint(cfg.checkpoint_dir)
        return rep

    # -- uniform outputs ---------------------------------------------------
    @property
    def result(self) -> Dict[str, np.ndarray]:
        """Dense host view of the job's current output values."""
        if self.epoch < 0:
            raise RuntimeError("no result before run()")
        return self._driver.result()

    def report(self, include_result: bool = True) -> RunReport:
        """Uniform report of the session's current state / last epoch.

        ``include_result=False`` skips materializing the dense host copy
        of the output (``session.result`` fetches it on demand).
        """
        if self.epoch < 0:
            raise RuntimeError("no report before run()")
        rep = RunReport(name=self.spec.name, mode=self._driver.mode,
                        epoch=self.epoch, backend=self._driver.backend(),
                        result=self._driver.result() if include_result
                        else {})
        self._driver.fill(rep)
        if self._last is not None and self._last.epoch == self.epoch:
            rep.seconds = self._last.seconds
        return rep

    # -- fault tolerance ---------------------------------------------------
    def checkpoint(self, path: Optional[str] = None) -> Path:
        """Atomically snapshot all preserved state (view/state, MRBG-Store,
        CPC accumulators, structure mirror) under ``path``."""
        from repro.api.ckpt import save_session
        target = path or self.config.checkpoint_dir
        if target is None:
            raise ValueError("no checkpoint path: pass one or set "
                             "RunConfig(checkpoint_dir=...)")
        return save_session(self, str(target))

    @classmethod
    def restore(cls, spec: Spec, path: str,
                config: Optional[RunConfig] = None) -> "Session":
        """Rebuild a session from :meth:`checkpoint` output; the next
        ``update(delta)`` resumes exactly where the snapshot left off."""
        from repro.api.ckpt import load_session
        return load_session(cls, spec, str(path), config)

    # -- escape hatches (engine internals, read-only use) ------------------
    @property
    def view(self) -> Optional[ResultView]:
        return getattr(self._driver, "view", None)

    @property
    def state(self) -> Optional[State]:
        return getattr(self._driver, "state", None)

    # -- preserved-state accounting (serving-layer hooks) ------------------
    @property
    def store(self) -> Optional[MRBGStore]:
        """The driver's MRBG-Store, if this execution path preserves one.

        Distributed sessions preserve one store *per shard* — use
        :attr:`stores` / the aggregate byte accessors there; this stays
        ``None`` for them.
        """
        drv = self._driver
        st = getattr(drv, "store", None)
        if st is None:
            st = getattr(getattr(drv, "job", None), "store", None)
        return st

    @property
    def stores(self) -> list:
        """Every MRBG-Store this session preserves: the per-shard slices of
        a distributed session, or ``[store]`` / ``[]`` otherwise."""
        sts = getattr(self._driver, "stores", None)
        if sts:
            return list(sts)
        st = self.store
        return [st] if st is not None else []

    def store_bytes(self) -> int:
        """MRBG file size including obsolete chunks, summed over shards
        (0 if nothing is preserved)."""
        return sum(s.file_bytes() for s in self.stores)

    def store_live_bytes(self) -> int:
        """Live chunk bytes, summed over shards."""
        return sum(s.live_bytes() for s in self.stores)

    def store_obsolete_bytes(self) -> int:
        """Obsolete (compactable) chunk bytes, summed over shards."""
        return sum(s.obsolete_bytes() for s in self.stores)

    def compact_store(self) -> int:
        """Offline MRBG compaction; returns the bytes reclaimed.  The
        multi-tenant server calls this on the fattest session when the
        shared store budget is exceeded."""
        return sum(s.compact() for s in self.stores)


# ---------------------------------------------------------------------------
# Drivers: one per engine path; each owns the preserved state
# ---------------------------------------------------------------------------

def _grow_mirror(drv, capacity: int) -> None:
    """Extend a driver's host structure mirror (``_keys``/``_values``/
    ``_valid``) with invalid rows up to ``capacity``."""
    capacity = int(capacity)
    n = drv._keys.shape[0]
    if capacity <= n:
        return
    pad = capacity - n
    drv._keys = np.concatenate(
        [drv._keys, np.zeros((pad,) + drv._keys.shape[1:], drv._keys.dtype)])
    drv._values = {
        name: np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
        for name, a in drv._values.items()}
    drv._valid = np.concatenate([drv._valid, np.zeros(pad, bool)])

class _OneStepMRBG:
    """run_onestep + MRBG-Store + incremental_onestep (§3.3/§3.4)."""

    kind = "onestep-mrbg"

    def __init__(self, spec: JobSpec, cfg: RunConfig):
        self.spec = spec
        self.cfg = cfg
        self.store = MRBGStore(spec.num_keys, cfg.value_bytes,
                               policy=cfg.store_policy, **cfg.store_kw())
        self.view: Optional[ResultView] = None
        self.mode = "onestep"
        self._counts: Optional[np.ndarray] = None
        self._affected = -1

    def backend(self) -> str:
        from repro.kernels import ops
        return ops.resolve_backend(self.cfg.backend)

    def run(self, inp: KV) -> None:
        res = run_onestep(self.spec, inp, preserve=True,
                          backend=self.cfg.backend)
        host = edges_to_host(res.edges)
        self.store.append(host["k2"], host["mk"], _v2_dict(host["v2"]))
        self.view = ResultView.from_job(self.spec.num_keys, res.results,
                                        res.counts)
        self._counts = np.asarray(res.counts)
        self.mode = "onestep"

    def update(self, delta: DeltaKV) -> None:
        self.store.reset_stats()
        stats = incremental_onestep(self.spec, delta, self.store, self.view,
                                    backend=self.cfg.backend)
        self._affected = int(stats.get("affected", 0))
        self._counts = self.view.counts
        self.mode = "incremental"

    def result(self) -> Dict[str, np.ndarray]:
        return self.view.as_dict()

    def fill(self, rep: RunReport) -> None:
        rep.counts = self._counts
        rep.affected_keys = self._affected
        rep.io = self.store.stats
        rep.store_bytes = self.store.file_bytes()
        rep.live_bytes = self.store.live_bytes()
        rep.store_batches = self.store.n_batches


class _OneStepAccumulator:
    """Accumulator-Reduce fast path: preserves only <K3,V3> (§3.5)."""

    kind = "onestep-accumulator"

    def __init__(self, spec: JobSpec, cfg: RunConfig):
        from repro.core.accumulator import AccumulatorJob
        self.spec = spec
        self.cfg = cfg
        self.job = AccumulatorJob(spec, backend=cfg.backend)
        self.mode = "onestep"

    @property
    def view(self) -> Optional[ResultView]:
        return self.job.view

    def backend(self) -> str:
        from repro.kernels import ops
        return ops.resolve_backend(self.cfg.backend)

    def run(self, inp: KV) -> None:
        self.job.initial_run(inp)
        self.mode = "onestep"

    def update(self, delta: DeltaKV) -> None:
        self.job.incremental_run(delta)
        self.mode = "accumulator"

    def result(self) -> Dict[str, np.ndarray]:
        return self.job.view.as_dict()

    def fill(self, rep: RunReport) -> None:
        rep.counts = self.job.view.counts
        rep.mrbg_on = False               # nothing preserved beyond <K3,V3>


class _IncrIter:
    """IncrIterJob: converge once, then fine-grain refresh (§5)."""

    kind = "incr-iter"

    def __init__(self, spec: IterSpec, cfg: RunConfig):
        self.spec = spec
        self.cfg = cfg
        self.job = None                   # built on run() (needs struct)
        self.mode = "iterative"
        self._iters = 0
        self._max_change: list = []
        self._logs: list = []

    @property
    def state(self) -> Optional[State]:
        return self.job.state if self.job is not None else None

    def backend(self) -> str:
        from repro.kernels import ops
        return ops.resolve_backend(self.cfg.backend)

    def _make_job(self, struct: KV):
        from repro.core.incr_iter import IncrIterJob
        return IncrIterJob(
            struct=struct, spec=self.spec,
            value_bytes=self.cfg.value_bytes,
            policy=self.cfg.store_policy,
            cpc_threshold=self.cfg.cpc_threshold,
            pdelta_threshold=self.cfg.pdelta_threshold,
            backend=self.cfg.backend, store_kw=self.cfg.store_kw())

    def run(self, struct: KV) -> None:
        self.job = self._make_job(struct)
        _, hist = self.job.initial_converge(max_iters=self.cfg.max_iters,
                                            tol=self.cfg.tol)
        self.mode = "iterative"
        self._iters = hist["iters"]
        self._max_change = hist["max_change"]
        self._logs = []

    def update(self, delta: DeltaKV) -> None:
        _, hist = self.job.refresh(delta,
                                   max_iters=self.cfg.refresh_iters_,
                                   tol=self.cfg.refresh_tol_)
        self.mode = hist["mode"]
        self._iters = hist["iters"]
        self._logs = hist.get("logs", [])
        self._max_change = []

    def grow_records(self, capacity: int) -> None:
        if self.job is not None:
            self.job.grow_records(capacity)

    def result(self) -> Dict[str, np.ndarray]:
        return self.job.state.to_host()

    def fill(self, rep: RunReport) -> None:
        rep.iters = self._iters
        rep.max_change = list(self._max_change)
        rep.logs = list(self._logs)
        if self._logs:
            rep.affected_keys = sum(l.n_affected_dks for l in self._logs)
            rep.io = IOStats(n_reads=sum(l.io_reads for l in self._logs),
                             bytes_read=sum(l.io_bytes for l in self._logs))
        rep.store_bytes = self.job.store.file_bytes()
        rep.live_bytes = self.job.store.live_bytes()
        rep.store_batches = self.job.store.n_batches
        rep.mrbg_on = self.job.mrbg_on


class _PlainIter:
    """plainMR recomp baseline: re-shuffles structure data every iteration
    and recomputes every epoch from scratch (Algorithm 5 cost model)."""

    kind = "plain-iter"

    def __init__(self, spec: IterSpec, cfg: RunConfig):
        self.spec = spec
        self.cfg = cfg
        self.state: Optional[State] = None
        self.mode = "plainMR"
        self._iters = 0
        self._max_change: list = []

    def backend(self) -> str:
        from repro.kernels import ops
        return ops.resolve_backend(self.cfg.backend)

    def run(self, struct: KV) -> None:
        self._keys = np.array(struct.keys)
        self._values = {n: np.array(a) for n, a in struct.values.items()}
        self._valid = np.array(struct.valid)
        self._converge(self.cfg.max_iters, self.cfg.tol)

    def _struct_kv(self) -> KV:
        return KV(jnp.asarray(self._keys),
                  {n: jnp.asarray(a) for n, a in self._values.items()},
                  jnp.asarray(self._valid))

    def _converge(self, max_iters: int, tol: float) -> None:
        self.state, hist = run_plain(self.spec, self._struct_kv(), None,
                                     max_iters=max_iters, tol=tol,
                                     backend=self.cfg.backend)
        self._iters = hist["iters"]
        self._max_change = hist["max_change"]

    def update(self, delta: DeltaKV) -> None:
        apply_delta_host(self._keys, self._values, self._valid, delta)
        # vanilla MR: recompute everything (under the refresh budget)
        self._converge(self.cfg.refresh_iters_, self.cfg.refresh_tol_)

    def grow_records(self, capacity: int) -> None:
        _grow_mirror(self, capacity)

    def result(self) -> Dict[str, np.ndarray]:
        return self.state.to_host()

    def fill(self, rep: RunReport) -> None:
        rep.iters = self._iters
        rep.max_change = list(self._max_change)
        rep.mrbg_on = False


class _Distributed:
    """shard_map + all_to_all prime loop over a MeshConfig (§4.3).

    ``update`` is kv-pair-level by default (``MeshConfig(refresh="fine")``):
    delta rows are partitioned by ``hash(project(SK))`` (Eq. 2), one
    ``all_to_all`` routes the re-Mapped delta edges to their owner shards,
    and each shard merges them against its **local** MRBG slice with the
    same kernels the single-device incremental path uses — no host-mirror
    repartition, no re-converge.  CPC filtering and the §5.2 auto MRBG-off
    fallback run globally over the per-shard results.

    ``MeshConfig(refresh="warm")`` — or an unstable map topology, or a
    tripped MRBG-off — re-partitions the mirror and re-converges warm from
    the current co-located state: the pre-MeshConfig behavior and the
    Fig. 8 rerun-side baseline.
    """

    kind = "distributed"

    def __init__(self, spec: IterSpec, cfg: RunConfig):
        if spec.replicate_state:
            raise ValueError(
                "replicate_state (all-to-one) specs broadcast their state; "
                "the co-partitioned distributed engine does not support "
                "them — run without a mesh (auto iterMR mode)")
        self.spec = spec
        self.cfg = cfg
        self.mc = cfg.mesh
        self.n_parts = self.mc.n_parts
        self.rows = (spec.num_state + self.n_parts - 1) // self.n_parts
        self.state_parts: Optional[Dict[str, np.ndarray]] = None
        # fine-grain preserved state: one MRBG slice per shard, keyed by
        # local ids (K2 // P); None until the first converge seeds them
        self.stores: Optional[list] = None
        self.cpc_accum = np.zeros(spec.num_state, np.float32)
        self.mrbg_on = True
        self.mode = "distributed"
        self._fine = (self.mc.refresh == "fine") and spec.stable_topology
        self._iters = 0
        self._max_change: list = []
        self._logs: list = []
        self._shuffle = ShuffleStats()
        self._step_cache: dict = {}       # converge steps, reused across epochs
        self._dx_step = None              # the delta-exchange jit, built once

    def backend(self) -> str:
        from repro.kernels import ops
        return ops.resolve_backend(self.cfg.backend)

    def _edge_bytes(self) -> int:
        # wire bytes per exchanged edge: K2 + MK (4+4), valid + sign (1+1),
        # plus the V2 payload
        return 10 + self.cfg.value_bytes

    def _rebuild_rev(self) -> None:
        from repro.core.incr_iter import build_reverse_index
        self.rev_indptr, self.rev_ids, self.dks_host = build_reverse_index(
            self.spec.project, self._keys, self._valid, self.spec.num_state)

    def run(self, struct: KV) -> None:
        self._keys = np.array(struct.keys)
        self._values = {n: np.array(a) for n, a in struct.values.items()}
        self._valid = np.array(struct.valid)
        self._rebuild_rev()
        if self.state_parts is None:      # may be pre-seeded by restore
            from repro.core.distributed import partition_state
            dks = jnp.arange(self.spec.num_state, dtype=jnp.int32)
            init = jax.tree.map(np.asarray, self.spec.init_state(dks))
            self.state_parts = partition_state(init, self.spec.num_state,
                                               self.n_parts)
        self._shuffle = ShuffleStats()
        self._logs = []
        self._converge(self.cfg.max_iters, self.cfg.tol)
        self.mode = "distributed"

    def _partition_cap(self) -> int:
        if self.mc.partition_cap is not None:
            return self.mc.partition_cap
        dks = np.asarray(jax.jit(self.spec.project)(jnp.asarray(self._keys)))
        pid = (dks.astype(np.uint32) % self.n_parts).astype(np.int32)
        load = np.bincount(pid[self._valid], minlength=self.n_parts)
        return next_bucket(max(int(load.max()), 1), 64)

    def _converge(self, max_iters: int, tol: float) -> None:
        from repro.core.distributed import partition_struct, run_distributed
        mc = self.mc
        parts = partition_struct(self.spec, self._keys, self._values,
                                 self._valid, self.n_parts,
                                 self._partition_cap())
        out, hist = run_distributed(
            self.spec, mc.mesh, parts, self.state_parts,
            axis=mc.axis, pod_axis=mc.pod_axis,
            shuffle_cap=mc.shuffle_cap, max_iters=max_iters,
            tol=tol, backend=self.cfg.backend, auto_grow=mc.auto_grow,
            preserve_last=self._fine, step_cache=self._step_cache)
        # np.array (not asarray): the fine path patches slices in place
        self.state_parts = {n: np.array(a) for n, a in out.items()}
        self._iters = hist["iters"]
        self._max_change = hist["max_change"]
        sh = self._shuffle
        sh.edges_exchanged += hist["sent"]
        sh.bytes_moved += hist["sent"] * self._edge_bytes()
        sh.exchange_seconds.extend(hist["exchange_seconds"])
        sh.shuffle_cap = hist["shuffle_cap"]
        sh.regrows += hist["regrows"]
        if self._fine:
            self._seed_stores(hist["last_edges"])

    def _seed_stores(self, last_edges) -> None:
        """Per-shard MRBG slices from the final iteration's received edges
        (``reduce(slice[p]) == state[p]`` by construction)."""
        cfg = self.cfg
        self.stores = [MRBGStore(self.rows, cfg.value_bytes,
                                 policy=cfg.store_policy, **cfg.store_kw())
                       for _ in range(self.n_parts)]
        for p, ed in enumerate(last_edges or []):
            if ed["k2"].size == 0:
                continue
            local = ((ed["k2"].astype(np.int64) - p)
                     // self.n_parts).astype(np.int32)
            self.stores[p].append(local, ed["mk"], _v2_dict(ed["v2"]))
        self.cpc_accum[:] = 0.0
        self.mrbg_on = True

    # -- refresh -----------------------------------------------------------
    def update(self, delta: DeltaKV) -> None:
        self._shuffle = ShuffleStats()
        self._logs = []
        snap = self._snapshot()
        try:
            if not (self._fine and self.mrbg_on and self.stores is not None):
                # warm re-converge: mirror repartition + prime loop (re-seeds
                # the per-shard slices when fine refresh is enabled, so
                # MRBG-off recovers exactly like §5.2's
                # rebuild-after-fallback)
                apply_delta_host(self._keys, self._values, self._valid,
                                 delta)
                self._rebuild_rev()
                self._converge(self.cfg.refresh_iters_, self.cfg.refresh_tol_)
                self.mode = "distributed-warm"
                return
            fell_back = self._fine_refresh(delta)
        except Exception:
            self._restore(snap)           # never leave the session diverged
            raise
        self.mode = "distributed-warm" if fell_back else "distributed-i2"

    def grow_records(self, capacity: int) -> None:
        n = self._keys.shape[0]
        _grow_mirror(self, capacity)
        if self._keys.shape[0] != n:
            self._rebuild_rev()

    def _snapshot(self):
        return (self._keys.copy(),
                {n: a.copy() for n, a in self._values.items()},
                self._valid.copy(),
                {n: a.copy() for n, a in self.state_parts.items()},
                self.cpc_accum.copy(),
                ([s.clone() for s in self.stores]
                 if self.stores is not None else None),
                self.mrbg_on)

    def _restore(self, snap) -> None:
        (self._keys, self._values, self._valid, self.state_parts,
         self.cpc_accum, self.stores, self.mrbg_on) = snap
        self._rebuild_rev()

    def _fine_refresh(self, delta: DeltaKV) -> bool:
        """Kv-pair-level refresh; returns True if it fell back to warm."""
        cfg = self.cfg
        apply_delta_host(self._keys, self._values, self._valid, delta)
        self._rebuild_rev()
        self._max_change = []
        max_iters, tol = cfg.refresh_iters_, cfg.refresh_tol_

        # iteration 1: delta input = delta structure data
        n_input = int(np.asarray(delta.valid).sum())
        changed = self._fine_iteration(delta, iteration=1, n_input=n_input)
        if changed is None:               # P_Δ blew past the threshold
            self._fallback_converge(max_iters, tol)
            return True

        # iterations >= 2: delta input = delta state data (reverse index)
        from repro.core.incr_iter import records_of_dks
        for it in range(2, max_iters + 1):
            if changed.size == 0 or (self._max_change
                                     and self._max_change[-1] < tol):
                break
            recs = records_of_dks(self.rev_indptr, self.rev_ids, changed)
            if recs.size == 0:
                break
            d2 = DeltaKV(self._keys[recs], recs,
                         {n: a[recs] for n, a in self._values.items()},
                         self._valid[recs], np.ones(recs.size, np.int8))
            changed = self._fine_iteration(d2, iteration=it,
                                           n_input=int(changed.size))
            if changed is None:
                self._fallback_converge(max_iters - it, tol)
                return True
        self._iters = len(self._logs)
        return False

    def _fallback_converge(self, max_iters: int, tol: float) -> None:
        """§5.2 MRBG-off recovery: warm re-converge + store re-seed (the
        distributed analogue of IncrIterJob._fallback_iterate)."""
        t0 = time.perf_counter()
        self._converge(max_iters, tol)
        self._logs.append(IterationLog(
            -1, 0, self.spec.num_state, self.spec.num_state, False,
            time.perf_counter() - t0))

    def _fine_iteration(self, delta, iteration: int, n_input: int):
        """One fine-grain iteration: delta exchange (device) + per-shard
        merges (host).  Returns emitted DKs, or None => fall back."""
        from repro.core.distributed import (
            delta_exchange_to_host, make_delta_exchange_step,
            merge_shards_parallel, partition_delta)
        spec, cfg, n_parts = self.spec, self.cfg, self.n_parts
        t0 = time.perf_counter()
        for s in self.stores:
            s.reset_stats()

        # phase 1: partition the delta rows by hash(project(SK)) (Eq. 2)
        # and exchange the re-Mapped edges; per-shard row capacity is
        # bucketed so the step traces once per bucket, not per row count
        keys = np.asarray(delta.keys)
        valid = np.asarray(delta.valid).astype(bool)
        dks = np.asarray(jax.jit(spec.project)(jnp.asarray(keys)))
        pid = (dks.astype(np.uint32) % np.uint32(n_parts)).astype(np.int32)
        load = np.bincount(pid[valid], minlength=n_parts)
        cap = next_bucket(max(int(load.max(initial=0)), 1),
                          cfg.delta_bucket_min)
        pk, pv, pvalid, psign = partition_delta(delta, n_parts, cap,
                                                project=spec.project)
        if self._dx_step is None:
            self._dx_step = make_delta_exchange_step(
                spec, self.mc.mesh, self.mc.axis,
                pod_axis=self.mc.pod_axis, backend=cfg.backend)
        tx = time.perf_counter()
        outs = self._dx_step(jnp.asarray(pk), jax.tree.map(jnp.asarray, pv),
                             jnp.asarray(pvalid), jnp.asarray(psign),
                             jax.tree.map(jnp.asarray, self.state_parts))
        shards, sent, _dropped = delta_exchange_to_host(outs)
        sh = self._shuffle
        sh.exchange_seconds.append(time.perf_counter() - tx)
        sh.edges_exchanged += sent
        sh.bytes_moved += sent * self._edge_bytes()
        sh.shuffle_cap = int(np.asarray(outs[0]).shape[1]) // n_parts

        # phase 2: per-shard MRBG merges (disjoint global key sets),
        # threaded across shards; CPC/state updates apply in shard order
        diff_fn = spec.difference
        affected_total = 0
        max_change = 0.0
        affected_parts = []
        merged = merge_shards_parallel(
            spec.reducer, self.stores, n_parts, shards,
            backend=cfg.backend, workers=self.mc.merge_workers)
        for p, aff, vals, _counts in merged:
            if aff.size == 0:
                continue
            affected_total += int(aff.size)
            local = (aff.astype(np.int64) // n_parts)
            old = {n: jnp.asarray(self.state_parts[n][p, local])
                   for n in self.state_parts}
            change = np.asarray(diff_fn(
                {n: jnp.asarray(a) for n, a in vals.items()}, old))
            if change.size:
                max_change = max(max_change, float(change.max()))
            self.cpc_accum[aff] += change
            for n, a in vals.items():
                self.state_parts[n][p, local] = a
            affected_parts.append(aff)

        if affected_total == 0:
            self._max_change.append(0.0)
            self._logs.append(IterationLog(
                iteration, n_input, 0, 0, True,
                time.perf_counter() - t0))
            return np.zeros(0, np.int64)
        self._max_change.append(max_change)

        # CPC (§5.3), global across shards: emit only above-threshold DKs
        affected_all = np.concatenate(affected_parts)
        emit_mask = self.cpc_accum[affected_all] > cfg.cpc_threshold
        emitted = np.sort(affected_all[emit_mask]).astype(np.int64)
        self.cpc_accum[emitted] = 0.0
        self._logs.append(IterationLog(
            iteration, n_input, affected_total, int(emitted.size), True,
            time.perf_counter() - t0,
            sum(s.stats.n_reads for s in self.stores),
            sum(s.stats.bytes_read for s in self.stores)))

        # auto MRBG-off (§5.2): fine-grain state stops paying off
        p_delta = emitted.size / max(spec.num_state, 1)
        if p_delta > cfg.pdelta_threshold:
            self.mrbg_on = False
            return None
        return emitted

    def result(self) -> Dict[str, np.ndarray]:
        from repro.core.distributed import unpartition_state
        return unpartition_state(self.state_parts, self.spec.num_state)

    def fill(self, rep: RunReport) -> None:
        rep.iters = self._iters
        rep.max_change = list(self._max_change)
        rep.logs = list(self._logs)
        if self._logs:
            rep.affected_keys = sum(l.n_affected_dks for l in self._logs)
            rep.io = IOStats(n_reads=sum(l.io_reads for l in self._logs),
                             bytes_read=sum(l.io_bytes for l in self._logs))
        if self.stores:
            rep.store_bytes = sum(s.file_bytes() for s in self.stores)
            rep.live_bytes = sum(s.live_bytes() for s in self.stores)
            rep.store_batches = sum(s.n_batches for s in self.stores)
        rep.mrbg_on = bool(self.stores) and self.mrbg_on
        rep.shuffle = self._shuffle


class _DistOneStep:
    """Per-shard one-step job on a mesh: `_OneStepMRBG`'s semantics, with
    the MRBGraph sliced across shards by the Eq. 1 hash.

    The initial run reuses the refresh machinery — every input record is an
    all-'+' delta against empty per-shard stores — so there is exactly one
    device program (the delta exchange) and one merge path, warm from
    epoch 0 onward.
    """

    kind = "distributed-onestep"

    def __init__(self, spec: JobSpec, cfg: RunConfig):
        self.spec = spec
        self.cfg = cfg
        self.mc = cfg.mesh
        self.n_parts = self.mc.n_parts
        self.rows = (spec.num_keys + self.n_parts - 1) // self.n_parts
        self.stores: Optional[list] = None
        self.view: Optional[ResultView] = None
        self.mode = "distributed"
        self.mrbg_on = True
        self._affected = -1
        self._shuffle = ShuffleStats()
        self._dx_step = None

    def backend(self) -> str:
        from repro.kernels import ops
        return ops.resolve_backend(self.cfg.backend)

    def _edge_bytes(self) -> int:
        return 10 + self.cfg.value_bytes

    def _fresh_stores(self) -> list:
        cfg = self.cfg
        return [MRBGStore(self.rows, cfg.value_bytes,
                          policy=cfg.store_policy, **cfg.store_kw())
                for _ in range(self.n_parts)]

    def run(self, inp: KV) -> None:
        self._shuffle = ShuffleStats()
        self.stores = self._fresh_stores()
        self.view = None
        delta = DeltaKV(np.asarray(inp.keys), np.asarray(inp.keys),
                        jax.tree.map(np.asarray, inp.values),
                        np.asarray(inp.valid),
                        np.ones(inp.capacity, np.int8))
        self._refresh(delta)
        self.mode = "distributed"

    def update(self, delta: DeltaKV) -> None:
        self._shuffle = ShuffleStats()
        snap = ([s.clone() for s in self.stores],
                ResultView(self.view.num_keys,
                           {n: a.copy() for n, a in self.view.values.items()},
                           self.view.valid.copy(), self.view.counts.copy()))
        try:
            self._refresh(delta)
        except Exception:
            self.stores, self.view = snap
            raise
        self.mode = "distributed-incr"

    def _refresh(self, delta: DeltaKV) -> None:
        from repro.core.distributed import (
            delta_exchange_to_host, make_delta_exchange_step,
            merge_shards_parallel, partition_delta)
        spec, cfg, n_parts = self.spec, self.cfg, self.n_parts
        for s in self.stores:
            s.reset_stats()

        keys = np.asarray(delta.keys)
        valid = np.asarray(delta.valid).astype(bool)
        pid = (keys.astype(np.uint32) % np.uint32(n_parts)).astype(np.int32)
        load = np.bincount(pid[valid], minlength=n_parts)
        cap = next_bucket(max(int(load.max(initial=0)), 1),
                          cfg.delta_bucket_min)
        pk, pv, pvalid, psign = partition_delta(delta, n_parts, cap)
        if self._dx_step is None:
            self._dx_step = make_delta_exchange_step(
                spec, self.mc.mesh, self.mc.axis,
                pod_axis=self.mc.pod_axis, backend=cfg.backend)
        tx = time.perf_counter()
        outs = self._dx_step(jnp.asarray(pk), jax.tree.map(jnp.asarray, pv),
                             jnp.asarray(pvalid), jnp.asarray(psign))
        shards, sent, _dropped = delta_exchange_to_host(outs)
        sh = self._shuffle
        sh.exchange_seconds.append(time.perf_counter() - tx)
        sh.edges_exchanged += sent
        sh.bytes_moved += sent * self._edge_bytes()
        sh.shuffle_cap = int(np.asarray(outs[0]).shape[1]) // n_parts

        affected_total = 0
        merged = merge_shards_parallel(
            spec.reducer, self.stores, n_parts, shards,
            backend=cfg.backend, workers=self.mc.merge_workers)
        for p, aff, vals, counts in merged:
            if aff.size == 0:
                continue
            affected_total += int(aff.size)
            if self.view is None:
                self.view = ResultView(
                    spec.num_keys,
                    {n: np.zeros((spec.num_keys,) + a.shape[1:], a.dtype)
                     for n, a in vals.items()},
                    np.zeros(spec.num_keys, bool),
                    np.zeros(spec.num_keys, np.int32))
            self.view.patch(aff, vals, counts)
        self._affected = affected_total

    def result(self) -> Dict[str, np.ndarray]:
        return self.view.as_dict() if self.view is not None else {}

    def fill(self, rep: RunReport) -> None:
        rep.affected_keys = self._affected
        if self.view is not None:
            rep.counts = self.view.counts
        if self.stores:
            rep.store_bytes = sum(s.file_bytes() for s in self.stores)
            rep.live_bytes = sum(s.live_bytes() for s in self.stores)
            rep.store_batches = sum(s.n_batches for s in self.stores)
            rep.io = IOStats(
                n_reads=sum(s.stats.n_reads for s in self.stores),
                bytes_read=sum(s.stats.bytes_read for s in self.stores))
        rep.shuffle = self._shuffle

"""Streaming serving benchmark: sustained updates/sec + refresh-latency
percentiles through `repro.stream.StreamSession`, per backend.

Four workloads:

  * ``wordcount``       — one-step / accumulator refresh over an evolving
    corpus (the steady-state latency-tail target: with bucketed delta
    shapes and a prewarmed ladder, p95 must sit near p50, with zero
    retraces after start()).
  * ``pagerank``        — iterative refresh with CPC (scheduler-heavy).
  * ``wordcount_hot``   — adversarial repeated-key bursts: each hot doc is
    rewritten several times inside one micro-batch, so the coalescer's
    first-'-'/last-'+' rule must cancel the interior rows.
  * ``wordcount_churn`` — adversarial insert-then-delete churn: docs are
    created and destroyed on previously-empty slots within one batch
    (full cancellation), mixed with live updates.

Retrace/recompile counters come from :mod:`repro.kernels.jitcache`; the
"steady" counters are taken after ``start()`` (initial run + prewarm), so
any nonzero value is a latency-tail bug, not warm-up.  Results land in
``BENCH_stream.json``:

    PYTHONPATH=src:. python benchmarks/stream_latency.py            # full
    PYTHONPATH=src:. python benchmarks/stream_latency.py --tiny     # CI smoke
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from benchmarks.common import CACHE_DIR, emit
from repro.api import RunConfig, StreamConfig
from repro.apps import pagerank as pr, wordcount as wc
from repro.kernels import jitcache
from repro.stream import DeltaRecord, QueueSource, StreamSession


def _serve(name: str, spec, data, source, config, stream) -> dict:
    ss = StreamSession(spec, data, source=source, config=config,
                       stream=stream)
    ss.start(background=False)      # initial run + prewarm compile here
    jit0 = jitcache.snapshot()      # steady-state baseline
    ss.drain(timeout=1200)          # sync mode: drain() is the consumer
    jit1 = jitcache.snapshot()
    m = ss.metrics.snapshot()
    actions = {d.action for d in ss.scheduler.decisions}
    emit(f"{name}.updates_per_sec", m["updates_per_sec"],
         f"batches={m['batches']},rows={m['rows_in']},actions={sorted(actions)}")
    emit(f"{name}.refresh_p50_ms", m["refresh_p50_ms"],
         f"p95={m['refresh_p95_ms']:.2f}ms")
    emit(f"{name}.latency_p50_ms", m["latency_p50_ms"],
         f"p95={m['latency_p95_ms']:.2f}ms")
    emit(f"{name}.retraces_steady", jit1["traces"] - jit0["traces"],
         f"compiles={jit1['compiles'] - jit0['compiles']},"
         f"retrace_batches={m['retrace_batches']}")
    if m["coalesce_savings"] > 0:
        emit(f"{name}.coalesce_savings", m["coalesce_savings"],
             f"rows_in={m['rows_in']},rows_engine={m['rows_engine']}")
    return {"updates_per_sec": m["updates_per_sec"],
            "refresh_p50_ms": m["refresh_p50_ms"],
            "refresh_p95_ms": m["refresh_p95_ms"],
            "latency_p50_ms": m["latency_p50_ms"],
            "latency_p95_ms": m["latency_p95_ms"],
            "batches": m["batches"], "rows_in": m["rows_in"],
            "coalesce_savings": m["coalesce_savings"],
            "refreshes": m["refreshes"],
            "retraces_steady": jit1["traces"] - jit0["traces"],
            "compiles_steady": jit1["compiles"] - jit0["compiles"],
            "retrace_batches": m["retrace_batches"],
            "compile_skips": ss.scheduler.compile_skips}


def _hot_source(mirror: np.ndarray, vocab: int, rng, epochs: int,
                hot: int, reps: int) -> QueueSource:
    """Repeated-key bursts: ``hot`` docs each rewritten ``reps`` times in a
    single record — only the first '-' and last '+' per doc matter."""
    src = QueueSource(capacity=epochs + 1)
    for e in range(epochs):
        rows = rng.choice(len(mirror), size=hot, replace=False)
        rids, bufs, signs = [], [], []
        for r in rows:
            cur = mirror[r].copy()
            for _ in range(reps):
                new = rng.integers(0, vocab, cur.shape).astype(np.int32)
                rids += [r, r]
                bufs += [cur, new]
                signs += [-1, 1]
                cur = new
            mirror[r] = cur
        src.push(DeltaRecord(record_ids=np.asarray(rids, np.int32),
                             values={"w": np.stack(bufs)},
                             sign=np.asarray(signs, np.int8), epoch=e))
    src.seal()
    return src


def _churn_source(mirror: np.ndarray, valid: np.ndarray, vocab: int, rng,
                  epochs: int, n_churn: int, n_live: int) -> QueueSource:
    """Insert-then-delete churn on initially-empty slots (first '+', last
    '-': the coalescer drops both rows) mixed with live updates."""
    src = QueueSource(capacity=epochs + 1)
    empty = np.nonzero(~valid)[0]
    live = np.nonzero(valid)[0]
    width = mirror.shape[1:]
    for e in range(epochs):
        rids, bufs, signs = [], [], []
        for s in rng.choice(empty, size=n_churn, replace=False):
            doc = rng.integers(0, vocab, width).astype(np.int32)
            rids += [s, s]
            bufs += [doc, doc]
            signs += [1, -1]            # created and destroyed in-batch
        for r in rng.choice(live, size=n_live, replace=False):
            new = rng.integers(0, vocab, width).astype(np.int32)
            rids += [r, r]
            bufs += [mirror[r].copy(), new]
            signs += [-1, 1]
            mirror[r] = new
        src.push(DeltaRecord(record_ids=np.asarray(rids, np.int32),
                             values={"w": np.stack(bufs)},
                             sign=np.asarray(signs, np.int8), epoch=e))
    src.seal()
    return src


def run_backend(backend: str, tiny: bool, cache_dir: str | None) -> dict:
    rng = np.random.default_rng(0)
    out = {}

    def rc(**kw) -> RunConfig:
        return RunConfig(backend=backend, value_bytes=4,
                         compilation_cache_dir=cache_dir, **kw)

    # -- wordcount: the steady-state latency target ------------------------
    n_docs, vocab, epochs = (64, 32, 3) if tiny else (1024, 512, 24)
    docs = rng.integers(0, vocab, (n_docs, 8)).astype(np.int32)
    spec, data, source = wc.make_stream(docs, vocab, frac=0.05, seed=1,
                                        epochs=epochs)
    batch_rows = 2 * max(1, int(n_docs * 0.05))
    out["wordcount"] = _serve(
        f"stream.wordcount.{backend}", spec, data, source,
        rc(),
        StreamConfig(max_batch_records=batch_rows,
                     max_batch_delay=0.005, policy="latency",
                     prewarm=True))

    # -- pagerank: iterative refresh ---------------------------------------
    s, pr_epochs = (128, 3) if tiny else (1024, 12)
    nbrs = pr.random_graph(s, 4, seed=3, p_edge=0.5)
    spec, struct, source = pr.make_stream(nbrs, frac=0.02, seed=5,
                                          epochs=pr_epochs)
    out["pagerank"] = _serve(
        f"stream.pagerank.{backend}", spec, struct, source,
        rc(max_iters=120, tol=1e-6, refresh_max_iters=60,
           cpc_threshold=0.01),
        StreamConfig(max_batch_records=2 * max(1, int(s * 0.02)),
                     max_batch_delay=0.005, policy="latency",
                     prewarm=True))

    # -- adversarial: repeated-key bursts ----------------------------------
    hot, reps, hot_epochs = (4, 4, 3) if tiny else (16, 4, 12)
    hot_docs = rng.integers(0, vocab, (n_docs, 8)).astype(np.int32)
    spec, data = wc.make_job(hot_docs, vocab)
    src = _hot_source(hot_docs.copy(), vocab, rng, hot_epochs, hot, reps)
    out["wordcount_hot"] = _serve(
        f"stream.wordcount_hot.{backend}", spec, data, src,
        rc(),
        StreamConfig(max_batch_records=2 * hot * reps,
                     max_batch_delay=0.005, policy="latency",
                     prewarm=True))

    # -- adversarial: insert-then-delete churn -----------------------------
    n_churn, n_live, ch_epochs = (2, 4, 3) if tiny else (8, 16, 12)
    ch_docs = rng.integers(0, vocab, (n_docs, 8)).astype(np.int32)
    ch_valid = np.arange(n_docs) < (3 * n_docs) // 4   # empty tail quarter
    spec = wc.make_spec(vocab)
    data = wc.make_input(np.arange(n_docs), ch_docs, ch_valid)
    src = _churn_source(ch_docs.copy(), ch_valid, vocab, rng, ch_epochs,
                        n_churn, n_live)
    out["wordcount_churn"] = _serve(
        f"stream.wordcount_churn.{backend}", spec, data, src,
        rc(),
        StreamConfig(max_batch_records=2 * (n_churn + n_live),
                     max_batch_delay=0.005, policy="latency",
                     prewarm=True))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="xla",
                    choices=("xla", "pallas", "both"))
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (seconds, not minutes)")
    ap.add_argument("--out", default=None,
                    help="write BENCH_stream.json here (default: only when "
                         "running --backend both full-size)")
    ap.add_argument("--cache-dir", default=CACHE_DIR,
                    help="persistent XLA executable cache directory "
                         "('' disables)")
    args = ap.parse_args()

    backends = (("xla", "pallas") if args.backend == "both"
                else (args.backend,))
    results = {"platform": jax.default_backend(),
               "note": "CPU wall-clock; pallas runs in interpret mode off-TPU",
               "tiny": args.tiny, "backends": {}}
    for bk in backends:
        results["backends"][bk] = run_backend(bk, args.tiny,
                                              args.cache_dir or None)
    results["jit"] = jitcache.snapshot()

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()

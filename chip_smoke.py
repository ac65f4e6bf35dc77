#!/usr/bin/env python3
"""Smoke run of the incremental engine on a TPU, through its public entry points.

One chip (the default) drives three phases, each on the default backend
(native Pallas kernels on a TPU) and again on ``backend="xla"`` as the
comparison:

  (a) wordcount through ``StreamSession`` on the one-step MRBG path: an
      initial run over 2**18 documents x 32 tokens, then 8 micro-batches
      that each rewrite 0.1% of the documents; the result must equal
      ``wordcount.oracle`` on the updated corpus exactly;
  (b) PageRank through ``Session(IterSpec)``: ``run`` + 2 ``update``s on a
      2**17-vertex graph (out-degree up to 16), 1% of the vertices rewired
      per delta; checked against ``pagerank.oracle``;
  (c) SSSP (the min reducer): ``run`` + ``update`` on a 2**15-vertex
      graph, checked against ``sssp.oracle``.

The two backends must agree: bitwise on integer-valued results, within a
fixed tolerance on float ranks and distances.  The default-backend phases
must between them trace every engine Pallas kernel, natively (the script
refuses interpret mode), and the xla phases none of them.

``--chips 4`` runs only the meshed path: PageRank (2**14 vertices) on a
4-device ``MeshConfig`` mesh, ``run`` + a fine-grain ``update``, compared
with a single-device ``Session`` fed the same data and delta, on the
default backend.

    python chip_smoke.py [--seed N]
    python chip_smoke.py --chips 4 [--seed N]

Earlier stdout lines are informational (sizes, kernels traced, compile
seconds, wall times ending in a device-to-host fetch of the result).  The
last line is ``{"ok": true, "device": {...}}``, printed only when every
phase and check passed.  Without a TPU the script exits non-zero and prints
no result.  The persistent compile cache goes to ``JAX_COMPILATION_CACHE_DIR``
where that is set, else to ``.jax_cache`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.api import (  # noqa: E402
    MeshConfig, RunConfig, Session, StreamConfig, make_delta,
)
from repro.apps import pagerank as pr, sssp, wordcount as wc  # noqa: E402
from repro.data.pipeline import DeltaStream  # noqa: E402
from repro.kernels import jitcache, ops  # noqa: E402
from repro.kernels.sort_u32 import default_interpret  # noqa: E402
from repro.stream import StreamSession  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"

# the engine's Pallas kernels: each must be traced by a default-backend phase
ENGINE_KERNELS = ("kernels.sort_lex", "kernels.segment_sum",
                  "kernels.segment_sum_counts", "kernels.segment_minmax")

WC_SIZES = dict(n_docs=1 << 18, doc_len=32, vocab=1 << 15, frac=0.001,
                batches=8)
GRAPH_SIZES = dict(vertices=1 << 17, max_out=16, frac=0.01)
# SSSP is cut to 2^15 vertices: its prime loop reduces unsorted segment ids,
# so the min kernel walks every (segment block x row tile) grid step, about
# 19 s per iteration on one v5e at 2^17 vertices; the cost falls with the
# square of the vertex count
SSSP_SIZES = dict(GRAPH_SIZES, vertices=1 << 15)
# --chips 4 checks the meshed path's agreement, not its scale: four chips
# are charged four times over, and each side compiles its own programs
MESH_SIZES = dict(GRAPH_SIZES, vertices=1 << 14, updates=1)
PR_UPDATES = 2
# PageRank refresh: no change-propagation filter and no fallback to a full
# re-iteration, so every update runs the fine-grain incremental iterative
# refresh (MRBG merges) and converges to the oracle's fixpoint
PR_CONFIG = dict(max_iters=200, tol=1e-5, cpc_threshold=0.0,
                 pdelta_threshold=1.0)
# float comparisons: the oracle tolerance of the repo's PageRank tests, the
# SSSP tests' absolute distance tolerance, and the largest relative gap
# allowed between two backends (or two layouts) that both converged
PR_ORACLE_RTOL = 1e-3
SSSP_ORACLE_ATOL = 1e-3
BACKEND_RTOL = 1e-4
MESH_RTOL = 2e-6


def check(ok: bool, what: str) -> None:
    print(f"  check {'PASS' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        sys.exit(f"chip_smoke: check failed: {what}; no result")


def rel_gap(a: np.ndarray, b: np.ndarray) -> float:
    """Largest relative difference; equal entries (infinities too) count 0."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    with np.errstate(invalid="ignore"):
        gap = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
    return float(np.where(a == b, 0.0, gap).max(initial=0.0))


class Phase:
    """Times one phase and reports which engine kernels it traced and how
    long XLA spent compiling inside it."""

    def __init__(self, name: str, backend):
        self.title = f"{name} [{ops.resolve_backend(backend)}]"

    def __enter__(self):
        self.traces0 = jitcache.trace_counts()
        self.compile0 = jitcache.compile_seconds_total()
        print(f"== {self.title}", flush=True)
        return self

    @staticmethod
    def timed(what: str, fn):
        """Run ``fn`` (which must end by fetching a result to the host, so
        the device work is finished) and print its wall time."""
        t0 = time.perf_counter()
        out = fn()
        print(f"  {what}: {time.perf_counter() - t0:.3f} s", flush=True)
        return out

    def __exit__(self, *exc):
        now = jitcache.trace_counts()
        self.kernels = sorted(k for k in now if k.startswith("kernels.")
                              and now[k] > self.traces0.get(k, 0))
        compile_s = jitcache.compile_seconds_total() - self.compile0
        print(f"  kernels traced: {self.kernels}", flush=True)
        print(f"  compile seconds: {compile_s:.3f}", flush=True)
        return False


# ---------------------------------------------------------------------------
# (a) wordcount: one-step MRBG refresh through StreamSession
# ---------------------------------------------------------------------------

def phase_wordcount(backend, seed: int, *, n_docs: int, doc_len: int,
                    vocab: int, frac: float, batches: int):
    rng = np.random.default_rng(seed)
    docs = rng.integers(0, vocab, (n_docs, doc_len)).astype(np.int32)
    spec, data, source = wc.make_stream(docs, vocab, frac=frac, seed=seed,
                                        epochs=batches)
    # one source epoch ('-' old, '+' new per rewritten doc) per micro-batch
    rows = 2 * max(1, int(n_docs * frac))
    ss = StreamSession(spec, data, source=source,
                       config=RunConfig(backend=backend, onestep_path="mrbg"),
                       stream=StreamConfig(max_batch_records=rows))
    with Phase("wordcount stream", backend) as ph:
        print(f"  docs={n_docs} x {doc_len} tokens ({n_docs * doc_len} "
              f"edges), vocab={vocab}, {batches} micro-batches of {rows} "
              f"delta rows", flush=True)
        ph.timed("run", lambda: (ss.start(background=False), ss.result))
        for b in range(batches):
            ph.timed(f"update {b}", lambda: (ss.step(), ss.result))
        ss.drain(timeout=600)
        got = ss.result["c"]
    check(ss.metrics.batches == batches
          and ss.metrics.refreshes == {"update": batches},
          f"{batches} micro-batches, each an incremental update "
          f"(got {ss.metrics.batches}: {ss.metrics.refreshes})")
    check(np.array_equal(got, wc.oracle(source.values["w"], vocab)),
          "wordcount counts == oracle on the updated corpus, exactly")
    return ph, got


# ---------------------------------------------------------------------------
# (b) PageRank and (c) SSSP: incremental iterative refresh through Session
# ---------------------------------------------------------------------------

def _graph_deltas(values, frac, seed, mutator, count, rid_offset=0):
    """``count`` signed deltas, each rewriting ``frac`` of the records, and
    the mirror of the fully updated values."""
    stream = DeltaStream(values, frac=frac, seed=seed, mutator=mutator)
    deltas = []
    for _ in range(count):
        rid, vals, sign = stream.delta()
        deltas.append(make_delta(rid + rid_offset, vals, sign))
    return deltas, stream.values


def phase_pagerank(backend, seed: int, *, vertices: int, max_out: int,
                   frac: float):
    nbrs = pr.random_graph(vertices, max_out, seed=seed)
    spec, struct = pr.make_job(nbrs)
    deltas, final = _graph_deltas({"nbrs": nbrs}, frac, seed,
                                  pr.graph_mutator(vertices), PR_UPDATES)
    sess = Session(spec, RunConfig(backend=backend, **PR_CONFIG))
    with Phase("pagerank", backend) as ph:
        print(f"  vertices={vertices}, out-degree <= {max_out} "
              f"({int((nbrs >= 0).sum())} edges), {PR_UPDATES} updates of "
              f"{frac:.0%} of the vertices", flush=True)
        rep = ph.timed("run", lambda: (sess.run(struct), sess.result)[0])
        print(f"  run: {rep.mode}, {rep.iters} iterations", flush=True)
        for i, d in enumerate(deltas):
            rep = ph.timed(f"update {i}",
                           lambda: (sess.update(d), sess.result)[0])
            print(f"  update {i}: {rep.mode}, {rep.iters} iterations",
                  flush=True)
        got = sess.result["r"]
    check(rep.mode == "i2", f"pagerank updates ran the incremental "
                            f"iterative refresh ({rep.mode})")
    gap = rel_gap(got, pr.oracle(final["nbrs"]))
    check(gap < PR_ORACLE_RTOL,
          f"pagerank ranks vs oracle: max relative error {gap:.3g} "
          f"< {PR_ORACLE_RTOL}")
    return ph, got


def _sssp_mutator(vertices: int):
    rewire = pr.graph_mutator(vertices)

    def mut(rng, rows, old):
        return {"nbrs": rewire(rng, rows, old)["nbrs"],
                "w": np.abs(rng.normal(1.0, 0.3, old["w"].shape)
                            ).astype(np.float32)}
    return mut


def phase_sssp(backend, seed: int, *, vertices: int, max_out: int,
               frac: float):
    nbrs, w = sssp.random_weighted_graph(vertices, max_out, seed=seed)
    spec, struct = sssp.make_job(nbrs, w, src=0)
    # structure record r is vertex r - 1 (record 0 is the virtual root)
    deltas, final = _graph_deltas({"nbrs": nbrs, "w": w}, frac, seed,
                                  _sssp_mutator(vertices), 1, rid_offset=1)
    sess = Session(spec, RunConfig(backend=backend, max_iters=300,
                                   tol=1e-7))
    with Phase("sssp", backend) as ph:
        print(f"  vertices={vertices}, out-degree <= {max_out}, 1 update "
              f"of {frac:.0%} of the vertices", flush=True)
        rep = ph.timed("run", lambda: (sess.run(struct), sess.result)[0])
        print(f"  run: {rep.mode}, {rep.iters} iterations", flush=True)
        rep = ph.timed("update 0",
                       lambda: (sess.update(deltas[0]), sess.result)[0])
        print(f"  update 0: {rep.mode}, {rep.iters} iterations", flush=True)
        got = sess.result["d"]
    want = sssp.oracle(final["nbrs"], final["w"], 0)
    finite = want < sssp.INF / 2
    err = float(np.abs(got[finite] - want[finite]).max(initial=0.0))
    check(err < SSSP_ORACLE_ATOL and bool((got[~finite] > sssp.INF / 2).all()),
          f"sssp distances vs oracle: max error {err:.3g} < "
          f"{SSSP_ORACLE_ATOL} on {int(finite.sum())} reachable vertices, "
          f"the rest unreachable")
    return ph, got


def one_chip(seed: int) -> None:
    if ops.resolve_backend(None) != "pallas":
        sys.exit("chip_smoke: the default backend does not resolve to "
                 "pallas (is REPRO_BACKEND set?); no result")
    default_phases = []
    for name, fn, kw in (("wordcount", phase_wordcount, WC_SIZES),
                         ("pagerank", phase_pagerank, GRAPH_SIZES),
                         ("sssp", phase_sssp, SSSP_SIZES)):
        ph_p, got_p = fn(None, seed, **kw)
        ph_x, got_x = fn("xla", seed, **kw)
        default_phases.append(ph_p)
        check(not ph_x.kernels, f"{name} on xla traced no Pallas kernel")
        if name == "wordcount":
            check(np.array_equal(got_p, got_x),
                  "wordcount pallas == xla, bitwise")
        else:
            gap = rel_gap(got_p, got_x)
            check(gap <= BACKEND_RTOL,
                  f"{name} pallas vs xla: max relative gap {gap:.3g} <= "
                  f"{BACKEND_RTOL} (bitwise: {np.array_equal(got_p, got_x)})")
    traced = set().union(*(ph.kernels for ph in default_phases))
    check(traced >= set(ENGINE_KERNELS),
          f"default backend traced every engine kernel natively "
          f"(missing: {sorted(set(ENGINE_KERNELS) - traced)})")


# ---------------------------------------------------------------------------
# --chips 4: meshed PageRank vs one device
# ---------------------------------------------------------------------------

def four_chips(seed: int, *, vertices: int, max_out: int, frac: float,
               updates: int, parts: int = 4) -> None:
    devs = jax.devices()
    if len(devs) < parts:
        sys.exit(f"chip_smoke: --chips {parts} needs {parts} devices, found "
                 f"{len(devs)}; no result")
    mesh = Mesh(np.array(devs[:parts]), ("data",))
    nbrs = pr.random_graph(vertices, max_out, seed=seed)
    spec, struct = pr.make_job(nbrs)
    deltas, _ = _graph_deltas({"nbrs": nbrs}, frac, seed,
                              pr.graph_mutator(vertices), updates)
    # per-(source, destination) exchange capacity: twice the mean share,
    # rounded up to a power of two, so the converge loop does not regrow
    share = int((nbrs >= 0).sum()) // parts ** 2
    cap = 1 << max(int(2 * share - 1).bit_length(), 6)
    # the default backend only: the xla leg's bitwise parity is pinned by
    # tests/test_dist_refresh.py on a virtual CPU mesh, and a second leg
    # would double the four-chip compile time
    ref = Session(spec, RunConfig(**PR_CONFIG))
    dist = Session(spec, RunConfig(mesh=MeshConfig(mesh, shuffle_cap=cap),
                                   **PR_CONFIG))
    with Phase(f"pagerank on a {parts}-device mesh vs one device",
               None) as ph:
        print(f"  vertices={vertices}, out-degree <= {max_out}, "
              f"{updates} fine-grain updates of {frac:.0%} of the "
              f"vertices, shuffle_cap={cap}", flush=True)
        r1 = ph.timed("single-device run",
                      lambda: (ref.run(struct), ref.result)[0])
        r2 = ph.timed("meshed run",
                      lambda: (dist.run(struct), dist.result)[0])
        pairs = [(r1, r2, ref.result["r"], dist.result["r"])]
        for i, d in enumerate(deltas):
            r1 = ph.timed(f"single-device update {i}",
                          lambda: (ref.update(d), ref.result)[0])
            r2 = ph.timed(f"meshed update {i}",
                          lambda: (dist.update(d), dist.result)[0])
            pairs.append((r1, r2, ref.result["r"], dist.result["r"]))
    for epoch, (r1, r2, a, b) in enumerate(pairs):
        print(f"  epoch {epoch}: {r1.mode} {r1.iters} it / {r2.mode} "
              f"{r2.iters} it, shuffle={r2.shuffle.edges_exchanged}e "
              f"dropped={r2.shuffle.dropped}", flush=True)
        # the Pallas reduce accumulates in tile-shaped blocks, so a sharded
        # layout may move the float reduction tree by a few ulp
        gap = rel_gap(b, a)
        check(r1.iters == r2.iters and gap <= MESH_RTOL,
              f"epoch {epoch}: same iteration count, meshed vs single "
              f"device max relative gap {gap:.3g} <= {MESH_RTOL} "
              f"(bitwise: {np.array_equal(a, b)})")
    check(r2.mode == "distributed-i2",
          f"meshed updates ran the fine-grain refresh ({r2.mode})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (found {dev.platform} "
                 f"{dev.device_kind!r}); no result")
    if default_interpret():
        sys.exit("chip_smoke: REPRO_PALLAS_INTERPRET asks for interpret "
                 "mode; the smoke runs the native kernels only; no result")
    count = len(jax.devices())
    print(f"device: {dev.platform} {dev.device_kind!r} x {count}", flush=True)
    print(f"compile cache: {jitcache.enable_persistent_cache(CACHE_DIR)}",
          flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed, **MESH_SIZES)
    else:
        one_chip(args.seed)
    print(f"total wall: {time.perf_counter() - t0:.1f} s, compile seconds "
          f"{jitcache.compile_seconds_total():.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

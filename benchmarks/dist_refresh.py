"""Distributed fine-grain refresh vs warm re-converge (Fig. 8 on a mesh).

Two meshed sessions receive the identical delta stream on a mesh over
every device the platform has (the four chips of a v5e host; on a CPU
host, set ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
running to get eight virtual devices):

  * ``fine`` — ``MeshConfig(refresh="fine")``: delta-only all_to_all +
    per-shard MRBG merges (the tentpole path; auto MRBG-off may still
    fall back at the largest ratios, and that is part of the story).
  * ``warm`` — ``MeshConfig(refresh="warm")``: host-mirror repartition +
    warm re-converge from the current state (the pre-fine baseline and
    the rerun side of the paper's Fig. 8 crossover).

Per delta ratio the benchmark reports p50/p95 update wall-clock for both,
plus shuffle traffic (the fine path should move |Δ|-proportional bytes,
the warm path |D|-proportional) and the modes actually taken.  Results
land in ``BENCH_dist.json``:

    PYTHONPATH=src:. python benchmarks/dist_refresh.py --out BENCH_dist.json
    PYTHONPATH=src:. python benchmarks/dist_refresh.py --tiny   # CI smoke
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import emit
from jax.sharding import Mesh
from repro.api import MeshConfig, RunConfig, Session
from repro.apps import pagerank as pr
from repro.core.incremental import make_delta


def _mesh() -> Mesh:
    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit(
            f"dist_refresh needs a mesh of >= 2 devices, found {len(devs)} "
            f"{devs[0].platform} device(s); on a CPU host set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 before running")
    return Mesh(np.array(devs), ("data",))


def _graph_delta(mirror: np.ndarray, rng, n_rows: int):
    s, f = mirror.shape
    rows = rng.choice(s, n_rows, replace=False)
    new = np.where(rng.random((n_rows, f)) < 0.6,
                   rng.integers(0, s, (n_rows, f)), -1).astype(np.int32)
    rid = np.repeat(rows.astype(np.int32), 2)
    buf = np.empty((2 * n_rows, f), np.int32)
    buf[0::2] = mirror[rows]
    buf[1::2] = new
    mirror[rows] = new
    return make_delta(rid, {"nbrs": buf},
                      np.tile(np.array([-1, 1], np.int8), n_rows))


def _pcts(xs) -> dict:
    a = np.asarray(xs, np.float64) * 1e3
    return {"p50_ms": float(np.percentile(a, 50)),
            "p95_ms": float(np.percentile(a, 95)),
            "mean_ms": float(a.mean())}


def run_ratio(backend: str, mesh: Mesh, nbrs: np.ndarray, ratio: float,
              epochs: int, shuffle_cap: int) -> dict:
    s = nbrs.shape[0]
    n_rows = max(1, int(s * ratio))
    # cpc_threshold is sized to the O(1) rank mass of this graph: small
    # enough for sub-0.1% rank error, large enough that delta propagation
    # dies out instead of tripping the §5.2 auto-off on every epoch
    kw = dict(backend=backend, max_iters=120, tol=1e-6,
              refresh_max_iters=60, cpc_threshold=1e-3)
    sessions = {
        "fine": Session(pr.make_job(nbrs)[0], RunConfig(
            mesh=MeshConfig(mesh, shuffle_cap=shuffle_cap), **kw)),
        # identical fine path with the phase-2 shard merges forced
        # sequential: the before/after of the threaded host loop
        "fine_seq": Session(pr.make_job(nbrs)[0], RunConfig(
            mesh=MeshConfig(mesh, shuffle_cap=shuffle_cap,
                            merge_workers=1), **kw)),
        "warm": Session(pr.make_job(nbrs)[0], RunConfig(
            mesh=MeshConfig(mesh, shuffle_cap=shuffle_cap,
                            refresh="warm"), **kw)),
    }
    out = {"ratio": ratio, "delta_rows": n_rows}
    converge_s = {}
    for name, sess in sessions.items():
        _, struct = pr.make_job(nbrs)
        t0 = time.perf_counter()
        sess.run(struct)
        converge_s[name] = time.perf_counter() - t0

    # identical delta stream for all sessions (+1 warm-up epoch so the
    # percentiles measure steady-state, not first-bucket compiles).
    # Sessions are interleaved per delta with a rotating order: the XLA
    # executable cache is process-global, so whichever session goes
    # first pays any fresh bucket compile that the others then reuse —
    # rotation spreads that cost evenly instead of biasing the A/B.
    rng = np.random.default_rng(17)
    mirror = nbrs.copy()
    deltas = [_graph_delta(mirror, rng, n_rows) for _ in range(epochs + 1)]
    names = list(sessions)
    stats = {n: {"secs": [], "modes": {}, "edges": 0, "bytes": 0}
             for n in names}
    for i, d in enumerate(deltas):
        r = i % len(names)
        for name in names[r:] + names[:r]:
            t0 = time.perf_counter()
            rep = sessions[name].update(d)
            dt = time.perf_counter() - t0
            if i == 0:
                continue               # warm-up epoch
            st = stats[name]
            st["secs"].append(dt)
            st["modes"][rep.mode] = st["modes"].get(rep.mode, 0) + 1
            st["edges"] += rep.shuffle.edges_exchanged
            st["bytes"] += rep.shuffle.bytes_moved
    for name in names:
        st = stats[name]
        out[name] = {**_pcts(st["secs"]), "modes": st["modes"],
                     "initial_converge_ms": converge_s[name] * 1e3,
                     "edges_exchanged": st["edges"],
                     "bytes_moved": st["bytes"]}
        emit(f"dist.{backend}.r{ratio:g}.{name}.p50_ms",
             out[name]["p50_ms"],
             f"p95={out[name]['p95_ms']:.1f}ms,modes={st['modes']}")
    f, w = out["fine"], out["warm"]
    out["speedup_p50"] = w["p50_ms"] / max(f["p50_ms"], 1e-9)
    out["bytes_ratio"] = f["bytes_moved"] / max(w["bytes_moved"], 1)
    out["merge_thread_speedup_p50"] = (
        out["fine_seq"]["p50_ms"] / max(f["p50_ms"], 1e-9))
    emit(f"dist.{backend}.r{ratio:g}.speedup_p50", out["speedup_p50"],
         f"bytes fine/warm={out['bytes_ratio']:.3f},"
         f"merge_threads={out['merge_thread_speedup_p50']:.2f}x")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="xla",
                    choices=("xla", "pallas", "both"))
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (seconds, not minutes)")
    ap.add_argument("--out", default=None,
                    help="write BENCH_dist.json here")
    args = ap.parse_args()

    mesh = _mesh()
    s, f, epochs, cap = (256, 4, 3, 512) if args.tiny \
        else (4096, 4, 8, 8192)
    # spans the Fig. 8 crossover: fine-grain refresh wins the small
    # ratios; past ~1% propagation trips the §5.2 auto-off and both
    # columns converge warm (by design)
    ratios = (0.01, 0.05) if args.tiny else (0.0005, 0.002, 0.01, 0.05)
    nbrs = pr.random_graph(s, f, seed=3, p_edge=0.6)

    backends = (("xla", "pallas") if args.backend == "both"
                else (args.backend,))
    dev = jax.devices()[0]
    results = {"platform": dev.platform, "device_kind": dev.device_kind,
               "devices": len(jax.devices()),
               "note": "wall-clock includes host merge + device exchange",
               "tiny": args.tiny, "graph": {"s": s, "f": f},
               "epochs": epochs, "backends": {}}
    for bk in backends:
        results["backends"][bk] = [
            run_ratio(bk, mesh, nbrs, r, epochs, cap) for r in ratios]

    if args.out:
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()

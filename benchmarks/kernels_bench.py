"""Kernel micro-benchmarks: wall time per kernel, plus an analytic roofline
floor (``tpu_est``) from the published peaks of the device it runs on —
printed only where ``repro.launch.mesh.PEAKS`` knows the device kind.

Run directly with ``--backend {xla,pallas,both}`` to time the dispatcher hot
paths (``ops.sort_pairs`` / ``ops.segment_reduce``) plus an end-to-end
``incremental_onestep`` refresh under each backend and record the comparison
to ``BENCH_backend.json`` — the start of the perf trajectory.
"""
from __future__ import annotations

import argparse
import json

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed
from repro.launch.mesh import PEAKS


def _roofline(flops: float, nbytes: float) -> str:
    """``tpu_est=..us,`` on a device with published peaks, else nothing."""
    peak = PEAKS.get(jax.devices()[0].device_kind)
    if peak is None:
        return ""
    t = max(flops / peak["flops_bf16"], nbytes / peak["hbm_bw"])
    return f"tpu_est={t * 1e6:.1f}us,"


def run():
    rng = np.random.default_rng(0)

    # segment_reduce: one [R,K]x[R,D] matmul per tile
    from repro.kernels.segment_reduce import segment_reduce_mxu
    n, d, k = 4096, 64, 1024
    seg = jnp.asarray(np.sort(rng.integers(0, k, n)), jnp.int32)
    vals = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)
    out, dt = timed(lambda: segment_reduce_mxu(seg, vals, k, rows=512,
                                               kblk=512).block_until_ready())
    flops = 2 * n * 512 * d * (k // 512)
    emit("kernel.segment_reduce.interp_s", dt * 1e6,
         _roofline(flops, n * d * 4 + k * d * 4) + f"flops={flops:.2e}")

    # flash attention
    from repro.kernels.flash_attention import flash_attention
    b, h, s, hd = 1, 4, 512, 64
    q = jnp.asarray(rng.normal(0, 1, (b, h, s, hd)), jnp.float32)
    kk = jnp.asarray(rng.normal(0, 1, (b, h, s, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (b, h, s, hd)), jnp.float32)
    out, dt = timed(lambda: flash_attention(q, kk, v, q_blk=128,
                                            kv_blk=128).block_until_ready())
    flops = 4 * b * h * s * s * hd
    emit("kernel.flash_attention.interp_s", dt * 1e6,
         _roofline(flops, 3 * b * h * s * hd * 4) + f"flops={flops:.2e}")

    # bitonic sort
    from repro.kernels.sort_u32 import sort_kv32
    n = 4096
    keys = jnp.asarray(rng.integers(0, 2**30, n), jnp.uint32)
    payload = jnp.arange(n, dtype=jnp.int32)
    out, dt = timed(lambda: sort_kv32(keys, payload)[0].block_until_ready())
    stages = int(np.log2(n)) * (int(np.log2(n)) + 1) // 2
    emit("kernel.sort_kv32.interp_s", dt * 1e6,     # VPU-bound estimate
         _roofline(0, stages * n * 8) + f"stages={stages}")

    # spmv
    from repro.kernels.spmv_ell import spmv_ell
    s_, f_, v_ = 4096, 8, 4096
    nbrs = rng.integers(0, v_, (s_, f_))
    nbrs[rng.random((s_, f_)) < 0.3] = -1
    contrib = rng.normal(0, 1, (s_, f_)).astype(np.float32)
    out, dt = timed(lambda: spmv_ell(jnp.asarray(nbrs, jnp.int32),
                                     jnp.asarray(contrib), v_,
                                     rows=256, kblk=1024
                                     ).block_until_ready())
    flops = 2 * s_ * f_ * 1024 * (v_ // 1024)
    emit("kernel.spmv_ell.interp_s", dt * 1e6,
         _roofline(flops, s_ * f_ * 8 + v_ * 4))


# ---------------------------------------------------------------------------
# Backend comparison: dispatcher hot paths + end-to-end incremental refresh
# ---------------------------------------------------------------------------

def _bench_ops(backend: str, results: dict) -> None:
    from repro.kernels import ops
    rng = np.random.default_rng(0)

    n = 4096
    k2 = jnp.asarray(rng.integers(0, 256, n), jnp.int32)
    mk = jnp.asarray(rng.integers(0, 1 << 20, n), jnp.int32)
    payload = {"v": jnp.asarray(rng.normal(0, 1, (n, 4)), jnp.float32)}
    fn = lambda: ops.sort_pairs(k2, mk, payload,
                                backend=backend).k2.block_until_ready()
    fn()                                     # compile
    _, dt = timed(fn, repeat=3)
    emit(f"ops.sort_pairs.{backend}_s", dt * 1e6)
    results["sort_pairs_us"] = dt * 1e6

    seg = jnp.asarray(np.sort(rng.integers(0, 1024, n)), jnp.int32)
    vals = {"v": jnp.asarray(rng.normal(0, 1, (n, 64)), jnp.float32)}
    valid = jnp.ones(n, bool)
    fn = lambda: ops.segment_reduce("sum", seg, vals, valid, 1024,
                                    backend=backend)[1].block_until_ready()
    fn()
    _, dt = timed(fn, repeat=3)
    emit(f"ops.segment_reduce.{backend}_s", dt * 1e6)
    results["segment_reduce_us"] = dt * 1e6


def _sweep_ops(backend: str, sizes, *, repeat: int = 2) -> list:
    """Size sweep of the dispatcher hot paths (2^10..2^20 rows by default).

    Records, per size: the shuffle sort, the segment reduce, and the
    composed ``shuffle_reduce``.  The point of the sweep is
    the *shape* of the curves — before the multi-tile sort, pallas fell
    off a cliff past one VMEM tile (pad-to-pow2-of-total); now the cost
    should scale as n log² n with no discontinuity at the old tile limit.
    """
    from repro.kernels import ops

    class _Sum:
        kind = "sum"

    rng = np.random.default_rng(0)
    key_cap, d = 1024, 8
    rows = []
    for n in sizes:
        rec = {"n": n}
        k2 = jnp.asarray(rng.integers(0, key_cap, n), jnp.int32)
        mk = jnp.asarray(rng.integers(0, 1 << 20, n), jnp.int32)
        vals = {"v": jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)}
        valid = jnp.ones(n, bool)
        sign = jnp.ones(n, jnp.int8)
        keys = jnp.asarray(np.arange(key_cap, dtype=np.int32))

        fn = lambda: ops.sort_pairs(k2, mk, vals,
                                    backend=backend).k2.block_until_ready()
        fn()
        _, dt = timed(fn, repeat=repeat)
        rec["sort_us"] = dt * 1e6

        seg = jnp.asarray(np.sort(rng.integers(0, key_cap, n)), jnp.int32)
        fn = lambda: ops.segment_reduce(
            "sum", seg, vals, valid, key_cap,
            backend=backend)[1].block_until_ready()
        fn()
        _, dt = timed(fn, repeat=repeat)
        rec["segment_reduce_us"] = dt * 1e6

        fn = lambda: ops.shuffle_reduce(
            _Sum(), k2, mk, vals, valid, sign, keys,
            backend=backend).counts.block_until_ready()
        fn()
        _, dt = timed(fn, repeat=repeat)
        rec["shuffle_reduce_us"] = dt * 1e6
        emit(f"ops.sweep.{backend}.n{n}.sort_us", rec["sort_us"],
             ",".join(f"{k}={v:.0f}" for k, v in rec.items()
                      if k.endswith("_us") and k != "sort_us"))
        rows.append(rec)
    return rows


def _bench_incremental_onestep(backend: str, results: dict) -> None:
    """End-to-end one-step refresh (wordcount, paper Section 3.3) through
    the repro.api Session façade."""
    from repro.api import RunConfig, Session, make_delta
    from repro.apps import wordcount as wc

    rng = np.random.default_rng(7)
    n_docs, vocab, length = 512, 256, 16
    docs = rng.integers(0, vocab, size=(n_docs, length)).astype(np.int32)
    spec, data = wc.make_job(docs, vocab)
    session = Session(spec, RunConfig(onestep_path="mrbg", value_bytes=4,
                                      backend=backend))

    _, dt = timed(lambda: session.run(data))
    emit(f"incremental_onestep.initial.{backend}_s", dt * 1e6)
    results["initial_us"] = dt * 1e6

    def delta_for(row, seed):
        new = np.random.default_rng(seed).integers(
            0, vocab, (1, length)).astype(np.int32)
        dk = np.repeat(np.asarray([row], np.int32), 2)
        sg = np.tile(np.array([-1, 1], np.int8), 1)
        buf = np.empty((2, length), docs.dtype)
        buf[0::2] = docs[[row]]
        buf[1::2] = new
        return make_delta(dk, {"w": jnp.asarray(buf)}, sg)

    session.update(delta_for(3, 1))          # compile the delta path
    _, dt = timed(lambda: session.update(delta_for(5, 2)), repeat=3)
    emit(f"incremental_onestep.refresh.{backend}_s", dt * 1e6)
    results["refresh_us"] = dt * 1e6


def run_backend_compare(backends, out_path: str = "BENCH_backend.json",
                        sweep_sizes=None):
    import jax
    report = {"platform": jax.default_backend(), "backends": {}}
    for bk in backends:
        res: dict = {}
        _bench_ops(bk, res)
        _bench_incremental_onestep(bk, res)
        if sweep_sizes:
            res["sweep"] = _sweep_ops(bk, sweep_sizes)
        report["backends"][bk] = res
    if ("xla" in report["backends"] and "pallas" in report["backends"]):
        x = report["backends"]["xla"]["refresh_us"]
        p = report["backends"]["pallas"]["refresh_us"]
        report["refresh_speedup_xla_over_pallas"] = p / max(x, 1e-9)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out_path}")
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", choices=("xla", "pallas", "both"),
                    default="both",
                    help="which shuffle/reduce backend(s) to time")
    ap.add_argument("--out", default="BENCH_backend.json")
    ap.add_argument("--micro", action="store_true",
                    help="also run the legacy kernel micro-benchmarks")
    ap.add_argument("--sweep", action="store_true",
                    help="size sweep 2^10..2^20 rows of the dispatcher "
                         "hot paths (the tile-cliff witness)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: sweep 2^10..2^14 only")
    args = ap.parse_args()
    if args.micro:
        run()
    backends = ("xla", "pallas") if args.backend == "both" else (args.backend,)
    sizes = None
    if args.tiny:
        sizes = [1 << p for p in range(10, 15)]
    elif args.sweep:
        sizes = [1 << p for p in range(10, 21)]
    run_backend_compare(backends, args.out, sweep_sizes=sizes)


if __name__ == "__main__":
    main()

"""Distributed shuffle engine: shard_map all_to_all == single-device.

Needs >1 XLA host device, so each test runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the flag must be set
before jax initializes, which has already happened in the pytest process).
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices; never a chip
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


COMMON = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.apps import pagerank as pr
from repro.core.distributed import (partition_struct, partition_state,
                                    unpartition_state, run_distributed)
from repro.core.iterative import run_iterative

S, F = 256, 5
nbrs = pr.random_graph(S, F, seed=11, p_edge=0.5)
spec = pr.make_spec(S)
state, _ = run_iterative(spec, pr.make_struct(nbrs), max_iters=60, tol=1e-7)
ref = np.asarray(state.values["r"])
skeys, svals, svalid = partition_struct(
    spec, np.arange(S, dtype=np.int32), {"nbrs": nbrs},
    np.ones(S, bool), 8, 64)
state0 = partition_state({"r": np.ones(S, np.float32)}, S, 8)
"""


def test_single_axis_shuffle():
    _run(COMMON + """
mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
out, hist = run_distributed(spec, mesh, (skeys, svals, svalid), state0,
                            axis="data", shuffle_cap=512, max_iters=60,
                            tol=1e-7)
got = unpartition_state({k: np.asarray(v) for k, v in out.items()}, S)["r"]
assert np.abs(got - ref).max() < 1e-5, np.abs(got - ref).max()
print("OK")
""")


def test_multipod_flattened_shuffle():
    _run(COMMON + """
mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("pod", "data"))
out, hist = run_distributed(spec, mesh, (skeys, svals, svalid), state0,
                            axis="data", pod_axis="pod", shuffle_cap=512,
                            max_iters=60, tol=1e-7)
got = unpartition_state({k: np.asarray(v) for k, v in out.items()}, S)["r"]
assert np.abs(got - ref).max() < 1e-5, np.abs(got - ref).max()
print("OK")
""")


def test_overflow_detection():
    """With auto_grow off, an undersized shuffle_cap must fail loudly."""
    _run(COMMON + """
mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
try:
    run_distributed(spec, mesh, (skeys, svals, svalid), state0,
                    axis="data", shuffle_cap=2, max_iters=2, tol=1e-7,
                    auto_grow=False)
    raise SystemExit("expected overflow error")
except RuntimeError as e:
    assert "overflow" in str(e)
print("OK")
""")


def test_overflow_auto_regrow():
    """Default auto_grow walks the cap up the bucket ladder instead of
    failing, and still matches the single-device fixed point."""
    _run(COMMON + """
mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
out, hist = run_distributed(spec, mesh, (skeys, svals, svalid), state0,
                            axis="data", shuffle_cap=2, max_iters=60,
                            tol=1e-7)
assert hist["regrows"] >= 1, hist["regrows"]
assert hist["shuffle_cap"] > 2
got = unpartition_state({k: np.asarray(v) for k, v in out.items()}, S)["r"]
assert np.abs(got - ref).max() < 1e-5, np.abs(got - ref).max()
print("OK")
""")


def test_small_mesh_lowering_lm():
    """2-3 archs lower+compile on an 8-device (2,4) mesh — the mini
    version of the production dry-run, actually runnable in CI."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
import repro.configs as C
from repro.launch.steps import input_specs
from repro.models.config import smoke_config, ShapeCell
import dataclasses

for arch in ["qwen3-1.7b", "gemma2-9b", "llama4-scout-17b-a16e"]:
    cfg = smoke_config(C.get(arch))
    cfg = cfg.replace(sharding=dataclasses.replace(
        cfg.sharding, batch=("data",)))
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    cell = ShapeCell("mini", 64, 8, "train")
    with mesh:
        step, args = input_specs(cfg, cell, mesh)
        compiled = jax.jit(step).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):   # older jax: one dict per device
            ca = ca[0]
        assert ca.get("flops", 0) > 0
    print(arch, "ok")
print("OK")
""")

"""Shared benchmark scaffolding: workloads, deltas, timing, CSV rows."""
from __future__ import annotations

import time
from pathlib import Path
from typing import Callable, Dict, List

import numpy as np
import jax.numpy as jnp

ROWS: List[Dict] = []

# persistent compile cache: a fixed path inside the checkout (a directory
# that moves never hits); JAX_COMPILATION_CACHE_DIR overrides it
CACHE_DIR = str(Path(__file__).resolve().parent.parent / ".jax_cache")


def emit(name: str, value: float, derived: str = ""):
    ROWS.append({"name": name, "us_per_call": value, "derived": derived})
    print(f"{name},{value:.1f},{derived}", flush=True)


def timed(fn: Callable, *args, repeat: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt


def pagerank_workload(s: int = 4096, f: int = 4, seed: int = 3,
                      p_edge: float = 0.6):
    from repro.apps import pagerank as pr
    nbrs = pr.random_graph(s, f, seed=seed, p_edge=p_edge)
    return pr.make_spec(s), pr.make_struct(nbrs), nbrs


def graph_update_delta(nbrs: np.ndarray, frac: float, seed: int = 9):
    """Paper-style delta: randomly rewire ``frac`` of the vertices."""
    from repro.core.incremental import make_delta
    s, f = nbrs.shape
    rng = np.random.default_rng(seed)
    k = max(1, int(s * frac))
    rows = rng.choice(s, k, replace=False)
    new_rows = np.where(rng.random((k, f)) < 0.6,
                        rng.integers(0, s, (k, f)), -1).astype(np.int32)
    dk = np.repeat(rows.astype(np.int32), 2)
    sg = np.tile(np.array([-1, 1], np.int8), k)
    buf = np.empty((2 * k, f), np.int32)
    buf[0::2] = nbrs[rows]
    buf[1::2] = new_rows
    nbrs2 = nbrs.copy()
    nbrs2[rows] = new_rows
    return make_delta(dk, {"nbrs": jnp.asarray(buf)}, sg), nbrs2

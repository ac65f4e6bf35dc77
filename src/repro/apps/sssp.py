"""Single-Source Shortest Path on the iterative engine (one-to-one).

Structure: SK = vertex id, SV = padded out-neighbors + weights.
State:     DK = vertex id, DV = {"d": dist}.
Map emits <j, d_i + w_ij>; Reduce is **min**; a virtual root record emits
<src, 0> so the source anchors the fixpoint.

Unlike the classic MapReduce SSSP that re-emits each vertex's own distance
(monotone non-increasing, wrong under edge deletions), contributions come
only from in-edges, so the MRBGraph merge handles deletions/weight increases
correctly — min is exactly the non-invertible reducer for which the paper's
fine-grain preserved state is *required* (no accumulator shortcut).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.engine import emit_multi
from repro.core.iterative import IterSpec
from repro.core.kvstore import KV, make_kv, min_reducer

INF = np.float32(3.4e38) / 4


def make_struct(nbrs: np.ndarray, w: np.ndarray, src: int,
                valid_rows=None) -> KV:
    """Row i: out-edges of vertex i-1; row 0 is the virtual root -> src.

    nbrs/w: [S, F]; the caller provides vertex rows; we prepend the root.
    """
    s = nbrs.shape[0]
    f = nbrs.shape[1]
    root_n = np.full((1, f), -1, np.int32)
    root_n[0, 0] = src
    root_w = np.zeros((1, f), np.float32)
    root_w[0, 0] = -INF   # so that d_root + w = 0 given d_root = INF sentinel
    nbrs2 = np.concatenate([root_n, nbrs]).astype(np.int32)
    w2 = np.concatenate([root_w, w.astype(np.float32)])
    if valid_rows is None:
        valid_rows = np.ones(s, bool)
    valid2 = np.concatenate([[True], valid_rows])
    return make_kv(np.arange(s + 1, dtype=np.int32),
                   {"nbrs": jnp.asarray(nbrs2), "w": jnp.asarray(w2)},
                   valid2)


def map_fn(struct: KV, dv, sign):
    nbrs = struct.values["nbrs"]             # [N, F]
    w = struct.values["w"]
    dist = dv["d"]                           # [N]
    is_root = (struct.keys == 0)
    # root emits exactly 0; vertices emit min(d_i, INF) + w.  Unreachable
    # sources contribute ~INF (never the min), keeping the emission topology
    # *state-independent* so stable_topology incremental replay is exact.
    contrib = jnp.where(is_root[:, None], 0.0,
                        jnp.minimum(dist[:, None], INF) + w)
    nvalid = (nbrs >= 0) & struct.valid[:, None]
    return emit_multi(nbrs, {"d": contrib.astype(jnp.float32)}, struct.keys,
                      nvalid, record_sign=sign)


def make_spec(num_vertices: int) -> IterSpec:
    return IterSpec(
        map_fn=map_fn,
        reducer=min_reducer(),
        # structure record r corresponds to vertex r-1 (root -> src handled
        # in map); its state key is r-1 (root projects to a scratch key 0 --
        # the root's map never reads state)
        project=lambda sk: jnp.maximum(sk - 1, 0),
        num_state=num_vertices,
        init_state=lambda dks: {"d": jnp.full(dks.shape[0], INF, jnp.float32)},
        difference=lambda c, p: jnp.where(
            (c["d"] > INF / 2) & (p["d"] > INF / 2), 0.0,
            jnp.abs(jnp.minimum(c["d"], INF) - jnp.minimum(p["d"], INF))),
        stable_topology=True,
        name="sssp",
    )


def make_job(nbrs: np.ndarray, w: np.ndarray, src: int, valid_rows=None):
    """Uniform app entry: ``(spec, data)`` ready for ``repro.api.Session``."""
    return make_spec(nbrs.shape[0]), make_struct(nbrs, w, src, valid_rows)


def oracle(nbrs: np.ndarray, w: np.ndarray, src: int,
           valid_rows=None) -> np.ndarray:
    """Bellman-Ford reference (all edges relaxed per round)."""
    s = nbrs.shape[0]
    if valid_rows is None:
        valid_rows = np.ones(s, bool)
    live = (nbrs >= 0) & np.asarray(valid_rows, bool)[:, None]
    u = np.nonzero(live)[0]
    v = nbrs[live]
    wv = np.asarray(w, np.float64)[live]
    d = np.full(s, np.float64(INF))
    d[src] = 0.0
    for _ in range(s):
        reach = d[u] < INF / 2
        cand = np.full(s, np.inf)
        np.minimum.at(cand, v[reach], d[u[reach]] + wv[reach])
        better = cand < d - 1e-12
        if not better.any():
            break
        d = np.where(better, cand, d)
    return d


def random_weighted_graph(num_vertices: int, max_out: int, seed: int = 0,
                          p_edge: float = 0.5):
    rng = np.random.default_rng(seed)
    nbrs = rng.integers(0, num_vertices, size=(num_vertices, max_out))
    mask = rng.random((num_vertices, max_out)) < p_edge
    nbrs = np.where(mask, nbrs, -1).astype(np.int32)
    w = np.abs(rng.normal(1.0, 0.3, size=(num_vertices, max_out))
               ).astype(np.float32)
    return nbrs, w

"""PageRank on an evolving Graph500 R-MAT graph (LDBC Graphalytics PR).

The system under test is the engine's own job declaration
(``repro.apps.pagerank``, the paper's Algorithm 2): rank R_j = d * sum
over in-edges of R_i / deg_i + (1 - d), with no redistribution of the
mass of vertices without out-edges.  What the benchmark owns is here.

- Graph: ``edgefactor * 2**scale`` R-MAT edges with initiator (a, b, c,
  d = 1 - a - b - c), vertex labels permuted by the seed, self-loops and
  duplicate edges removed; each vertex keeps at most ``row_width`` of
  its out-edges (chosen at random), stored as one row of neighbour ids.
- Event ``rewire``: one vertex with out-edges, chosen uniformly, gets
  new out-neighbours: a '-' row with the old ones, a '+' row with as many
  distinct new ones drawn from R-MAT's destination distribution.
- Reference: float64 power iteration to a fixpoint on the graph after
  every applied event.  The number compared is the largest relative gap
  between the session's ranks and the reference's.
- Control: the same power iteration with ranks and sums in bfloat16,
  the precision below the configuration's float32.
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from perfbench.harness.schedule import seed_seq

DAMPING = 0.85
VALUE_WIDTH = 1                        # one float32 column, "r"
REF_TOL = 1e-13
REF_MAX_ITERS = 2000


def rmat(rng, scale: int, edges: int, a: float, b: float, c: float):
    """Raw R-MAT (source, destination) pairs, before relabelling."""
    src = np.zeros(edges, np.int64)
    dst = np.zeros(edges, np.int64)
    for level in range(scale):
        u = rng.random(edges)
        src |= (u >= a + b).astype(np.int64) << level
        dst |= (((u >= a) & (u < a + b)) | (u >= a + b + c)).astype(
            np.int64) << level
    return src, dst


class Job:
    rows_per_event = 2
    value_width = VALUE_WIDTH

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.api import RunConfig, StreamConfig
        from repro.apps import pagerank as pr
        if traffic["event"] != "rewire" or traffic["choice"] != "uniform":
            raise ValueError(f"pagerank has no event {traffic['event']!r} "
                             f"with choice {traffic['choice']!r}")
        self.scale = int(cfg["scale"])
        self.n = 1 << self.scale
        self.width = int(cfg["row_width"])
        self.a, self.b, self.c = (float(cfg[k]) for k in ("a", "b", "c"))
        rng = np.random.default_rng(seed_seq(seed, 1))
        src, dst = rmat(rng, self.scale, int(cfg["edgefactor"]) * self.n,
                        self.a, self.b, self.c)
        self.label = rng.permutation(self.n)
        src, dst = self.label[src], self.label[dst]
        key = np.unique((src * self.n + dst)[src != dst])
        src, dst = key // self.n, key % self.n
        order = np.lexsort((rng.random(src.size), src))
        src, dst = src[order], dst[order]
        pos = np.arange(src.size) - np.searchsorted(src, src, side="left")
        keep = pos < self.width
        self.nbrs0 = np.full((self.n, self.width), -1, np.int32)
        self.nbrs0[src[keep], pos[keep]] = dst[keep]
        self.edges = int(keep.sum())
        self.dropped_share = 1.0 - self.edges / max(src.size, 1)
        self.describe = (f"{self.n} vertices, {self.edges} edges, "
                         f"{self.dropped_share:.4f} of the simple graph's "
                         f"edges past row_width dropped")
        self.spec, self.data = pr.make_job(self.nbrs0)
        self.run_config = RunConfig(**cfg["run_config"])
        self.stream_config = StreamConfig(**cfg["stream_config"])
        self.rng = np.random.default_rng(seed_seq(seed, 2))
        self.ev_vertex = np.zeros(0, np.int64)
        self.ev_new = np.zeros((0, self.width), np.int32)

    def destinations(self, k: int, exclude: int) -> np.ndarray:
        """``k`` distinct destinations other than ``exclude``."""
        p1 = 1.0 - self.a - self.c           # P(destination bit = 1)
        got = np.zeros(0, np.int64)
        while got.size < k:
            bits = self.rng.random((4 * k, self.scale)) < p1
            raw = (bits.astype(np.int64) << np.arange(self.scale)).sum(1)
            cand = np.concatenate([got, self.label[raw]])
            cand = cand[cand != exclude]
            _, first = np.unique(cand, return_index=True)
            got = cand[np.sort(first)]
        return got[:k]

    def records(self, n: int) -> List:
        """Events 0..n-1 as DeltaRecords, in order, epoch = sequence."""
        from repro.stream.source import DeltaRecord
        deg = (self.nbrs0 >= 0).sum(1)
        movable = np.nonzero(deg > 0)[0]
        verts = movable[self.rng.integers(0, movable.size, n)]
        mirror = self.nbrs0.copy()
        new = np.full((n, self.width), -1, np.int32)
        out = []
        sign = np.array([-1, 1], np.int8)
        for i, v in enumerate(verts):
            new[i, :deg[v]] = self.destinations(int(deg[v]), int(v))
            rows = np.stack([mirror[v], new[i]])
            mirror[v] = new[i]
            out.append(DeltaRecord(np.array([v, v], np.int32),
                                   {"nbrs": rows}, sign, epoch=i))
        self.ev_vertex, self.ev_new = verts, new
        return out

    def graph(self, applied: int) -> np.ndarray:
        """The neighbour rows after events 0..applied-1."""
        nbrs = self.nbrs0.copy()
        rev = self.ev_vertex[:applied][::-1]
        ids, at = np.unique(rev, return_index=True)   # last event of each
        nbrs[ids] = self.ev_new[applied - 1 - at]
        return nbrs

    def _edges(self, applied: int):
        nbrs = self.graph(applied)
        live = nbrs >= 0
        deg = live.sum(1)
        src = np.nonzero(live)[0]
        return src, nbrs[live].astype(np.int64), deg

    def reference(self, applied: int) -> Dict[str, np.ndarray]:
        src, dst, deg = self._edges(applied)
        w = 1.0 / deg[src]
        r = np.ones(self.n)
        for _ in range(REF_MAX_ITERS):
            new = DAMPING * np.bincount(dst, weights=r[src] * w,
                                        minlength=self.n) + (1 - DAMPING)
            done = np.abs(new - r).max() < REF_TOL
            r = new
            if done:
                break
        return {"r": r}

    def control(self, applied: int) -> Dict[str, np.ndarray]:
        """The reference with ranks and sums in bfloat16, on the default
        device, for as many iterations as float64 needs at most."""
        import jax
        import jax.numpy as jnp
        src, dst, deg = self._edges(applied)
        bf = jnp.bfloat16
        s, d = jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)
        inv = jnp.asarray(1.0 / deg[src], bf)

        @jax.jit
        def run(r):
            def body(_, r):
                acc = jax.ops.segment_sum(r[s] * inv, d, self.n)
                return (DAMPING * acc + (1 - DAMPING)).astype(bf)
            return jax.lax.fori_loop(0, REF_MAX_ITERS, body, r)

        r = run(jnp.ones(self.n, bf))
        return {"r": np.asarray(r.astype(jnp.float32), np.float64)}

    def compare(self, result: Dict[str, np.ndarray],
                ref: Dict[str, np.ndarray]) -> Dict[str, tuple]:
        got = np.asarray(result["r"], np.float64)
        gaps = np.abs(got - ref["r"]) / ref["r"]
        print("rank gap quantiles p50/p90/p99/max, mean: "
              + "/".join(f"{q:.3g}" for q in np.quantile(
                  gaps, [0.5, 0.9, 0.99, 1.0]))
              + f", {gaps.mean():.3g}", file=sys.stderr)
        return {"rank_gap_max": (float(gaps.max()), RANK_GAP_LIMIT)}


# provisional, between one sound run's gap (9.7e-7) and the bfloat16
# control's smallest (0.0225, eight seeds on a v5e); PERF.md §7 says what
# is still to be read
RANK_GAP_LIMIT = 1e-2

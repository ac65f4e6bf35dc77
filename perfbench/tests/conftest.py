"""The benchmark's own CPU checks.  Run from the checkout's root:

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def read(kind: str, name: str) -> dict:
    return json.loads((ROOT / "perfbench" / kind / f"{name}.json")
                      .read_text())


WORDCOUNT = "wordcount-hibench-mrbg"

# PageRank on R-MAT (LDBC Graphalytics PageRank on a Graph500 graph), no
# cell yet: its generator and reference at the settings a cell would use
PAGERANK = {"job": "pagerank", "edgefactor": 16, "a": 0.57, "b": 0.19,
            "c": 0.19, "damping": 0.85,
            "run_config": {"max_iters": 120, "tol": 1e-6,
                           "refresh_max_iters": 60, "cpc_threshold": 0.01},
            "stream_config": {"policy": "paper", "max_batch_records": 4096}}

# each job kind at a size a CPU test can hold: (configuration, its
# overrides, a traffic mix)
JOBS = {
    "wordcount": (read("configs", WORDCOUNT),
                  {"documents": 512, "min_words": 2, "max_words": 9,
                   "vocab": 256},
                  read("traffic", "rewrite-backlog")),
    "pagerank": (PAGERANK, {"scale": 9, "row_width": 16},
                 {"event": "rewire", "choice": "uniform"}),
}

# each cell of BENCHMARK.json at a size a CPU test can hold, with enough
# documents that a batch stays under the rerun crossover
SMALL = {
    "wc-mrbg.rewrite-backlog": {
        "config": {**JOBS["wordcount"][1], "documents": 4096,
                   "run_config": {**JOBS["wordcount"][0]["run_config"],
                                  "backend": "xla"}},
        "traffic": {"events": 200, "lead_events": 20, "lead_seconds": 1,
                    "warm_up_batches": [20]}},
}


def job(kind: str, seed: int, **extra):
    """A job of ``kind`` at its small size, on the xla backend (``extra``
    overrides more)."""
    from perfbench.harness import registry
    cfg, over, traffic = JOBS[kind]
    cfg = {**cfg, **over, **extra}
    cfg["run_config"] = {**cfg["run_config"], "backend": "xla"}
    return registry.job_module(cfg["job"]).Job(cfg, traffic, seed)

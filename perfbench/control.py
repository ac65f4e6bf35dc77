#!/usr/bin/env python3
"""Read a cell's control: the plain reference in the program's place,
computed in the precision below the configuration's.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <run_seconds>

For each seed it makes the data and every event of the cell's traffic
(warm-up, lead-in and window), computes the reference and the job's
lower-precision control, and compares them with the cell's own
comparison.  One JSON line per seed; a sound limit makes ``correct``
false on every one.  Benchmark runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(workload: str, seeds, seconds: float, *, root=ROOT,
             overrides=None, require_chip=True):
    from perfbench.harness import cell as C, registry
    _, entry, cfg, traffic = C.load(workload, root, overrides)
    C.devices(entry, require_chip, root)
    for seed in seeds:
        job = registry.job_module(cfg["job"], root).Job(cfg, traffic, seed)
        n = (sum(int(k) for k in traffic.get("warm_up_batches", []))
             + len(C.offsets_of(traffic, seconds, seed)[0]))
        job.records(n)
        checks = job.compare(job.control(n), job.reference(n))
        yield {"seed": seed,
               "correct": all(v <= lim for v, lim in checks.values()),
               "checks": {k: {"value": v, "limit": lim}
                          for k, (v, lim) in checks.items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from perfbench.harness.cell import NoChip
    try:
        for line in readings(args.workload,
                             [int(s) for s in args.seeds.split(",")],
                             args.seconds):
            print(json.dumps(line), flush=True)
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Cells, configurations, traffic and metrics are those of BENCHMARK.json at
the checkout's root.  The last line of standard output is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced) and, last, ``checks``: each number compared
with the plain reference, beside its limit.  The same numbers end
standard error.  Without a TPU, or with fewer chips than the cell asks
for, the run exits with code 3 and prints no result.  The persistent
compile cache is ``JAX_COMPILATION_CACHE_DIR`` where that is set, else
``.jax_cache`` at the checkout's root.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from perfbench.harness.cell import NoChip, run_cell
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START, root=ROOT)
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

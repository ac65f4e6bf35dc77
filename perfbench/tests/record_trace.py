#!/usr/bin/env python3
"""Record the small TPU trace that test_trace.py reduces.

    python3 perfbench/tests/record_trace.py      # on a TPU

Writes ``data/small.xplane.pb`` and ``data/small.json`` (the host spans,
the window's start on the host clock and its length) beside this file:
three runs of one jitted sort, each followed by a 20 ms sleep inside a
``poll`` span, between a ``perfbench.t0`` annotation and the close.
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))


@jax.jit
def small_sort(x):
    return jnp.sort(x) * 2


def main() -> int:
    from perfbench.harness.trace import find_xplane
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU")
    x = jnp.arange(1 << 20, dtype=jnp.int32)[::-1]
    small_sort(x).block_until_ready()
    out = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation("perfbench.t0"):
        t0 = time.perf_counter()
    spans = []
    for _ in range(3):
        a = time.perf_counter()
        small_sort(x).block_until_ready()
        b = time.perf_counter()
        time.sleep(0.02)
        spans += [("refresh", a, b), ("poll", b, time.perf_counter())]
    closed = time.perf_counter() - t0
    jax.profiler.stop_trace()
    (HERE / "data").mkdir(exist_ok=True)
    shutil.copy(find_xplane(out), HERE / "data" / "small.xplane.pb")
    (HERE / "data" / "small.json").write_text(json.dumps(
        {"t0": t0, "closed": closed, "spans": spans}))
    shutil.rmtree(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

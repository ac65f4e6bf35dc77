#!/usr/bin/env python3
"""Record the scoped TPU trace that test_phases.py reduces.

    python3 perfbench/tests/record_scoped_trace.py      # on a TPU

Writes ``data/scoped.xplane.pb`` and ``data/scoped.json`` (the window's
length, the jitted program's name and the program's spans on the host
clock) beside this file.  Between a ``perfbench.t0`` annotation and the
close: three micro-batches, each a ``repro.test.step`` span holding a
``repro.test.device`` span, in which the jitted ``scoped`` runs a sort
and a key search under the named scopes ``shuffle_reduce/sort`` and
``shuffle_reduce/route``, and a ``repro.test.sleep`` span of 20 ms; then
10 ms outside any span.  Prints whether ``xplane_pb2`` loads here.
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
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]


@jax.jit
def scoped(x, keys):
    with jax.named_scope("shuffle_reduce"):
        with jax.named_scope("sort"):
            y = jnp.sort(x)
        with jax.named_scope("route"):
            return jnp.searchsorted(keys, y)


def main() -> int:
    from perfbench.harness.phases import load_xplane_pb2
    from perfbench.harness.trace import find_xplane
    from repro.core import spans
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_scoped_trace: no TPU")
    print(f"xplane_pb2 loads: {load_xplane_pb2() is not None}", flush=True)
    x = jnp.arange(1 << 20, dtype=jnp.int32)[::-1]
    keys = jnp.arange(0, 1 << 20, 1 << 10, dtype=jnp.int32)
    scoped(x, keys).block_until_ready()
    spans.take()
    out = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation("perfbench.t0"):
        t0 = time.perf_counter()
    for i in range(3):
        with spans.span("repro.test.step", epoch=i):
            with spans.span("repro.test.device"):
                scoped(x, keys).block_until_ready()
            with spans.span("repro.test.sleep"):
                time.sleep(0.02)
    time.sleep(0.01)
    closed = time.perf_counter() - t0
    jax.profiler.stop_trace()
    recorded, _ = spans.take()
    (HERE / "data").mkdir(exist_ok=True)
    shutil.copy(find_xplane(out), HERE / "data" / "scoped.xplane.pb")
    (HERE / "data" / "scoped.json").write_text(json.dumps(
        {"t0": t0, "closed": closed, "program": "scoped",
         "spans": [[s.name, s.parent, s.epoch, s.start, s.end]
                   for s in recorded]}))
    shutil.rmtree(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

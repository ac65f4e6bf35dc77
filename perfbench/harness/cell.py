"""One run of one cell: set-up, the measured window, the check, the line.

Set-up makes the data and the events from the seed, runs the initial job
through ``repro.stream.StreamSession``, refreshes once with a full batch
of the traffic's events and runs the traffic's lead-in.  The window
follows.  Once it has closed and the device's peak memory is read, the
session is freed and the job's plain reference recomputes the result from
the seed's data and every applied event; the session's own result is
compared with it.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Optional

import jax
import numpy as np

from perfbench.harness import drive, registry, schedule
from perfbench.harness.peaks import peaks
from perfbench.harness.source import ScheduledSource
from perfbench.harness.trace import TraceSummary, summarize
from repro.kernels import jitcache

CACHE_DIR = ".jax_cache"
TRACE_DIR = ".perfbench_trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class RunView:
    """What a per-layer metric reader reads."""

    window: drive.Window
    trace: Optional[TraceSummary]
    job: object
    peaks: Optional[dict]


def check_device(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} "
                     f"{devs[0].device_kind!r}; no result")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devs)}; no result")
    peaks(devs[0].device_kind)          # an unknown kind is an error
    return devs[:chips]


def end_to_end(win: drive.Window, setup_s: float) -> Dict[str, float]:
    """Freshness over the window's events; delta rows per second over the
    window's refreshes (step start through the result fetch), each
    counted by the share of its span inside the window: the window opens
    at a step boundary, and the one refresh that is running as it closes
    counts in part."""
    lat = win.reflected - win.due
    rows = sum(r.rows * min(1.0, (win.t0 + win.seconds - r.start)
                            / (r.end - r.start))
               for r in win.refreshes)
    return {
        "freshness_p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "freshness_p95_ms": float(np.percentile(lat, 95)) * 1e3,
        "delta_rows_per_s": rows / win.seconds,
        "setup_s": setup_s,
    }


def load(name: str, root: Path, overrides: Optional[dict]):
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic),
    with ``overrides`` (``{"config": {...}, "traffic": {...}}``) applied:
    the CPU tests run the harness at small sizes through it."""
    bench = registry.load_benchmark(root)
    cell = registry.workload(bench, name)
    cfg = registry.config(bench, cell["config"], root)
    traffic = registry.traffic(cell["traffic"], root)
    cfg.update((overrides or {}).get("config", {}))
    traffic.update((overrides or {}).get("traffic", {}))
    return bench, cell, cfg, traffic


def devices(cell: dict, require_chip: bool, root: Path) -> list:
    """The cell's chips, with the persistent compile cache turned on; or,
    for the CPU tests, the default device."""
    if not require_chip:
        return jax.devices()[:1]
    devs = check_device(cell["chips"])
    jitcache.enable_persistent_cache(Path(root) / CACHE_DIR)
    return devs


def offsets_of(traffic: dict, seconds: float, seed: int):
    """(due offsets of every event, first window event, lead seconds).

    The lead-in takes ``lead_seconds`` before the window: for an open
    loop, the first seconds of the same traffic, of which the first
    ``lead_burst`` events are due at once (so the lead-in's first batch
    is as large as a steady one); for a backlog, ``lead_events`` events
    due at once, with the window's own backlog due as the lead-in ends.
    """
    lead = float(traffic["lead_seconds"])
    if traffic["arrivals"] == "backlog":
        first = int(traffic["lead_events"])
        offsets = np.concatenate([
            np.zeros(first),
            lead + schedule.window_offsets(traffic, seconds, seed)])
        return offsets, first, lead
    offsets = schedule.window_offsets(traffic, lead + seconds, seed)
    offsets[:int(traffic.get("lead_burst", 0))] = 0.0
    return offsets, int(np.searchsorted(offsets, lead)), lead


def start(name: str, job, traffic: dict, n_events: int):
    """Make the events, run the initial job, then refresh once per batch
    of ``warm_up_batches`` (counts of the traffic's first events), before
    any arrival is scheduled: the merge programs of the bucket pairs the
    window's batches fall in compile there.  Returns (session, source,
    events used)."""
    from repro.stream import StreamSession
    warm = [int(n) for n in traffic.get("warm_up_batches", [])]
    source = ScheduledSource(job.records(sum(warm) + n_events))
    ss = StreamSession(job.spec, job.data, source=source,
                       config=job.run_config, stream=job.stream_config,
                       name=name)
    ss.start(background=False)
    used = 0
    for n in warm:
        source.release(used, np.full(n, time.perf_counter()))
        ss.drain(timeout=600)
        used += n
    return ss, source, used


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = registry.ROOT,
             require_chip: bool = True,
             overrides: Optional[dict] = None,
             log: Callable[[str], None] = None) -> dict:
    """Run cell ``name``; returns the result line as a dict."""
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    bench, cell, cfg, traffic = load(name, root, overrides)
    devs = devices(cell, require_chip, root)
    dev = devs[0]
    compiles = drive.CompileCounter()

    job = registry.job_module(cfg["job"], root).Job(cfg, traffic, seed)
    offsets, first, lead = offsets_of(traffic, seconds, seed)
    ss, source, base = start(name, job, traffic, len(offsets))
    hist = ss.session.history
    log(f"set-up: {getattr(job, 'describe', '')}; initial run "
        f"{hist[0].seconds:.1f} s; warm-up refreshes "
        f"{[round(h.seconds, 2) for h in hist[1:]]} s; "
        f"{time.perf_counter() - t_start:.1f} s since start; "
        f"lead-in {lead} s of {first} events; "
        f"compiles so far {jitcache.snapshot()}")

    trace_dir = Path(root) / TRACE_DIR / f"{name}-{seed}"
    opened = []

    def on_open():
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # host spans are annotations
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        opened.append(time.perf_counter())

    win = drive.run_window(ss, source, offsets, first, lead, seconds,
                           compiles, base=base, trace=trace,
                           hold_at_close=traffic["arrivals"] == "backlog",
                           on_open=on_open,
                           on_close=jax.profiler.stop_trace if trace
                           else None)
    setup_s = opened[0] - t_start
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    applied = ss.metrics.last_epoch + 1
    result = ss.result
    refreshes = ss.metrics.refreshes
    del ss, source
    gc.collect()

    summary = None
    if trace:
        summary = summarize(trace_dir, win.t0, win.closed, win.spans)
        shutil.rmtree(trace_dir, ignore_errors=True)

    unreflected = np.isnan(win.reflected)
    if unreflected.any():               # late past the grace: a lower bound
        win.reflected[unreflected] = win.seconds + drive.GRACE_S
    checks = job.compare(result, job.reference(applied))
    correct = all(v <= lim for v, lim in checks.values())

    chosen = registry.cell_metrics(bench, name)
    if trace:
        view = RunView(win, summary, job,
                       peaks(dev.device_kind) if require_chip else None)
        metrics = {}
        for m in chosen["per_layer"]:
            value = registry.metric_reader(m["name"], root)(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        e2e = end_to_end(win, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in chosen["end_to_end"]}

    lat = (win.reflected - win.due) * 1e3
    spans = [round(r.end - r.start, 3) for r in win.refreshes]
    log(f"window: {len(win.due)} events, {len(win.refreshes)} refreshes "
        f"({refreshes}), refresh s {spans}, "
        f"rows {[r.rows for r in win.refreshes]}, iters "
        f"{[r.iters for r in win.refreshes]}, compiles {win.compiles} "
        f"(by refresh end {[r.compiles_before for r in win.refreshes]}), "
        f"freshness p50/p95/max ms {np.percentile(lat, 50):.1f}/"
        f"{np.percentile(lat, 95):.1f}/{lat.max():.1f}, "
        f"memory peak {memory_peak}, applied {applied}")
    out = {
        "correct": bool(correct),
        "attempted": int(len(win.due)),
        "failed": int(unreflected.sum()),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": memory_peak},
    }
    if summary is not None:
        out["device"].update(busy_s=summary.busy_s,
                             window_s=summary.window_s)
        out["breakdown"] = {"device_ops": [list(o) for o in summary.ops],
                            "idle_gaps": [list(g) for g in summary.gaps]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    return out

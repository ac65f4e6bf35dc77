"""Find every piece of a cell by its name.

- ``BENCHMARK.json`` at the checkout root names the cells, their
  configuration and traffic, and the metrics;
- a configuration is the JSON file its ``configs`` entry names; its
  ``job`` key names the job kind, ``jobs/<job>.py``;
- a traffic mix is ``traffic/<name>.json``;
- a per-layer metric ``<name>`` is read by ``metrics/<name>.py``, or, for
  a name with a suffix such as ``refresh_ms_p50.backlog``, by the file of
  the part before the first dot when no file of the full name exists.

Nothing here lists the files: adding one of each kind needs no edit.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]


def bench_dir(root: Path) -> Path:
    return Path(root) / "perfbench"


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((Path(root) / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((bench_dir(root) / "traffic" / f"{name}.json")
                      .read_text())


def _load(path: Path, tag: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{tag}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{tag}_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def job_module(kind: str, root: Path = ROOT) -> ModuleType:
    return _load(bench_dir(root) / "jobs" / f"{kind}.py", "job")


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read(run) -> Optional[float]`` of per-layer metric ``name``."""
    d = bench_dir(root) / "metrics"
    path = d / f"{name}.py"
    if not path.is_file():
        path = d / f"{name.split('.', 1)[0]}.py"
    return _load(path, "metric").read


def cell_metrics(bench: dict, cell: str) -> Dict[str, List[dict]]:
    """The end-to-end and per-layer metric entries that ``cell`` reports.

    An end-to-end metric without a ``workloads`` key is in every cell; a
    per-layer metric without one is in every cell that reports the metric
    it moves.
    """
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"end_to_end": e2e, "per_layer": layer}

"""Configurations, traffic mixes and per-layer metrics are found by name
from files of their own: a new file of each kind is picked up with no
edit to any file that is there."""
import hashlib
import json
import shutil

from perfbench.harness import registry


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_files_are_picked_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(registry.ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = registry.load_benchmark()
    before = digest(root)

    b = root / "perfbench"
    cfg = registry.config(bench, "wordcount-hibench-mrbg")
    cfg.update(name="wordcount-hibench-small", documents=1024)
    (b / "configs" / "wordcount-hibench-small.json").write_text(
        json.dumps(cfg))
    traffic = registry.traffic("rewrite-backlog")
    traffic.update(arrivals="poisson", events_per_s=5, gap_seed=1)
    (b / "traffic" / "rewrite-slow.json").write_text(json.dumps(traffic))
    (b / "metrics" / "rows_per_refresh.py").write_text(
        "def read(run):\n    return 7.0\n")
    bench["configs"].append({"name": "wordcount-hibench-small",
                             "source": "x",
                             "file": "perfbench/configs/"
                                     "wordcount-hibench-small.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "wc.slow", "config":
                               "wordcount-hibench-small",
                               "traffic": "rewrite-slow", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "rows_per_refresh", "unit": "rows",
                               "better": "higher", "source": "host_clock",
                               "layer": "stream",
                               "moves": "delta_rows_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = digest(root)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    got = registry.load_benchmark(root)
    entry = registry.workload(got, "wc.slow")
    assert registry.config(got, entry["config"], root)["documents"] == 1024
    assert registry.traffic(entry["traffic"], root)["events_per_s"] == 5
    assert registry.metric_reader("rows_per_refresh", root)(None) == 7.0
    chosen = registry.cell_metrics(got, "wc.slow")
    # no workloads key: reported wherever delta_rows_per_s is
    assert "rows_per_refresh" in {m["name"] for m in chosen["per_layer"]}
    assert "freshness_p50_ms" not in {m["name"]
                                      for m in chosen["end_to_end"]}
    job = registry.job_module("wordcount", root)
    assert job.Job.rows_per_event == 2


def test_suffixed_metric_falls_back_to_its_base_reader():
    read = registry.metric_reader("compiles_in_window.backlog")
    assert read is not None


def test_every_declared_metric_has_a_reader():
    bench = registry.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))

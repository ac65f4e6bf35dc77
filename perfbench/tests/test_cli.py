"""The command's contract at its edges: no TPU, no program."""
import json
import os
import shutil
import subprocess
import sys

from perfbench.harness import registry

ARGS = ["--workload", "wc-mrbg.rewrite-backlog", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(registry.ROOT / "perfbench"
                                            / "run.py"), *ARGS],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 3 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(registry.ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "perfbench/run.py", *ARGS],
                       capture_output=True, text=True, env=env,
                       cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())

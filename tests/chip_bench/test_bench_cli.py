"""``run.py`` prints no result where it must not: on a host without a
TPU, and in a directory that holds only the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_helpers import ROOT
from benchmarks.chip import layout

BENCH = layout.benchmark()
CELL = BENCH["workloads"][0]["name"]


def _results(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            out.append(obj)
    return out


@pytest.mark.parametrize("where", ["checkout", "benchmark_only"])
def test_run_exits_nonzero_without_tpu(where, tmp_path):
    root = ROOT
    if where == "benchmark_only":
        root = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
        for path in BENCH["paths"]:
            shutil.copytree(ROOT / path, root / path,
                            ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELL,
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _results(proc.stdout) == []

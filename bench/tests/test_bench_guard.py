"""``bench/run.py`` exits nonzero and prints no result where it cannot
measure: on a machine without a TPU, and in a checkout that holds only
the benchmark (no program)."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "qwen3-1.7b-w7.decode-long", "--seed", "3000000000",
        "--seconds", "1", "--trace", "0"]


def _run(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_no_result():
    p = _run(REPO)
    assert p.returncode == 2, p.stderr[-2000:]
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("keep", [["BENCHMARK.json", "bench"]])
def test_benchmark_alone_no_result(tmp_path, keep):
    for name in keep:
        src = REPO / name
        if src.is_dir():
            shutil.copytree(src, tmp_path / name,
                            ignore=shutil.ignore_patterns("__pycache__"))
        else:
            shutil.copy(src, tmp_path / name)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)

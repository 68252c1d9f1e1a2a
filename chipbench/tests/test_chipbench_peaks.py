"""The peak table refuses a device it does not know, and a run refuses a
machine without a TPU or without the rest of the repository."""
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_known_kind():
    assert peaks.peak("TPU v5 lite").hbm_bytes_per_s == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v6 lite", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks.peak(kind)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "gcn2x16-reddit.train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_run_exits_nonzero_on_cpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "No module named 'repro'" in r.stderr

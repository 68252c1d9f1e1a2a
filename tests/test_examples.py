"""Examples smoke: the public entry point must keep running end-to-end.

quickstart.py is the README's first command — it forces its own 8-device
CPU ring (XLA flag set before the jax import), so it runs through the
same subprocess harness as the multidev scripts.  train_gnn.py does the
same, so it runs in its own interpreter too.
"""
import json
import os
import subprocess
import sys

from test_system import _run  # tests/ is on sys.path under pytest

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def test_quickstart_runs_clean():
    out = _run("quickstart.py", directory=EXAMPLES)
    assert "max |err| vs dense oracle" in out
    assert "autotuned knobs" in out


def test_train_gnn_metrics_json_reports_its_compiles(tmp_path):
    """examples/train_gnn.py --metrics-json carries JAX's compile events
    (``repro.obs.watch_compiles``) and the aggregations' backward paths
    beside the step times."""
    out = tmp_path / "metrics.json"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "train_gnn.py"),
         "--steps", "2", "--metrics-json", str(out),
         "--workdir", str(tmp_path / "ckpt")],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    snap = json.loads(out.read_text())
    hist = snap["histograms"]
    assert hist["train.step_seconds"]["count"] == 2
    assert snap["counters"]["mgg.aggregate.vjp{path=transposed}"] >= 2
    for phase in ("trace_s", "lower_s", "backend_s"):
        assert hist[f"jax.compile.{phase}"]["count"] >= 1, phase
        assert hist[f"jax.compile.{phase}"]["sum"] > 0, phase

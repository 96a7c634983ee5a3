"""One ``--tiny`` end-to-end rehearsal of each runner on the CPU: the
last line has the contract's keys and can never be taken for a result."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(tmp_path, *extra, env_extra=None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable] + command[1:] + ["--out", str(tmp_path)]
        + list(extra), cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_train_runner_tiny(tmp_path, trace):
    proc = _run(tmp_path, "--workload", "ptb-medium-train-1chip", "--seed",
                "2147483659", "--seconds", "2", "--trace", str(trace),
                "--tiny")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) - {"breakdown"} == KEYS
    assert last["correct"] is False          # a rehearsal, never a result
    assert last["device"]["platform"] == "cpu"
    assert "correct=True" in lines[-2]       # the run's own checks passed
    assert last["attempted"] > 0 and last["failed"] == 0
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert "driver.dispatch_share" in last["metrics"]
        assert "train_throughput" not in last["metrics"]
    else:
        assert set(last["metrics"]) == {"train_throughput", "setup_s"}
        assert last["metrics"]["train_throughput"]["value"] > 0
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"}


def test_no_tpu_no_result(tmp_path):
    """Off the TPU and not a rehearsal: non-zero, and no result line."""
    proc = _run(tmp_path, "--workload", "ptb-medium-train-1chip", "--seed",
                "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout

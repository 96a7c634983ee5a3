"""The ``--tiny`` rehearsal of ``granite-4.0-h-small-train-8k-1chip`` on
the CPU: the ``train_lm`` runner end to end at toy sizes — the plain
reference first, the short job whose parameters are compared, the
measured job — and a last line that has the contract's keys and can
never be taken for a result."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELL = "granite-4.0-h-small-train-8k-1chip"


def _run(tmp_path, *extra):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable] + command[1:] + ["--out", str(tmp_path)]
        + list(extra), cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_train_lm_runner_tiny(tmp_path, trace):
    proc = _run(tmp_path, "--workload", CELL, "--seed", "2147483659",
                "--seconds", "2", "--trace", str(trace), "--tiny")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) - {"breakdown"} == KEYS
    assert last["correct"] is False          # a rehearsal, never a result
    assert last["device"]["platform"] == "cpu"
    assert "correct=True" in lines[-2]       # the run's own checks passed
    assert last["attempted"] > 0 and last["failed"] == 0
    # the comparison that decides: parameters after the first steps
    said = [ln for ln in lines if "parameters' change" in ln]
    assert len(said) == 1 and "the measured job repeats the first: True" in \
        proc.stdout
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"])
        # the program's counters are read on any device; the device
        # trace's metrics only on the chip
        assert 0 < last["metrics"]["moe.row_fill"]["value"] <= 1
        assert last["metrics"]["moe.rows_overflow"]["value"] == 0
        assert "driver.device_wait_share" in last["metrics"]
        assert "moe.experts_roofline" not in last["metrics"]
        assert "train_throughput" not in last["metrics"]
    else:
        assert set(last["metrics"]) == {"train_throughput", "setup_s"}
        assert last["metrics"]["train_throughput"]["value"] > 0
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"}


def test_the_control_goes_through_the_runners_own_comparison(tmp_path):
    """``tools/precision_reading.py``: the product and the reference one
    precision below the stated one, each judged by ``train_lm``'s
    ``params_correct`` against the configuration's limits, leaf by leaf.
    (Which verdict each gets is a reading of the chip, PERF.md: toy
    sizes on a CPU separate nothing.)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/precision_reading.py",
         "--workload", CELL, "--seeds", "2147483659", "--tiny",
         "--out", str(tmp_path)], cwd=ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    kinds = set(line["moved_by_kind"])
    assert {"mixer.A_log", "mixer.dt_bias", "mixer.D", "mixer.conv_w",
            "mixer.norm_w", "experts.router", "embed"} <= kinds
    for side in ("product", "state_bf16"):
        assert isinstance(line[side]["correct"], bool)
        assert set(line[side]["worst_by_kind"]) == kinds
        leaves = json.load(open(os.path.join(
            tmp_path, f"{side}.seed2147483659.steps{line['steps']}"
                      f".lr{line['lr']}.json")))
        assert "layers.0.mixer.A_log" in leaves

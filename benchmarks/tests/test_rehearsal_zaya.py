"""The ``--tiny`` rehearsal of ``zaya1-8b-train-8k-1chip`` on the CPU:
the ``train_lm`` runner end to end at toy sizes with the ``zaya``
builder and reference — the plain reference first, the short job whose
parameters are compared leaf by leaf, the measured job — the counters
its readers take, and a last line that can never be taken for a
result."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import lib  # noqa: E402

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELL = "zaya1-8b-train-8k-1chip"
NEW = ["cca.device_share", "cca.mix_share", "cca.attend_roofline",
       "moe.route_share", "moe.expert_imbalance"]


def _run(tmp_path, *command):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable] + list(command) + ["--out", str(tmp_path)],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
def test_train_lm_runner_tiny(tmp_path, trace):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    proc = _run(tmp_path, *command[1:], "--workload", CELL, "--seed",
                "2147483659", "--seconds", "2", "--trace", str(trace),
                "--tiny")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) - {"breakdown"} == KEYS
    assert last["correct"] is False          # a rehearsal, never a result
    assert "correct=True" in lines[-2]       # the run's own checks passed
    assert last["attempted"] > 0 and last["failed"] == 0
    said = [ln for ln in lines if "parameters' change" in ln]
    assert len(said) == 1 and "the measured job repeats the first: True" in \
        proc.stdout
    # every kind of leaf the model has was compared: the router's, the
    # convolutions', the temperature, the scalings
    kinds = {ln.split(":")[0].strip() for ln in lines if "(limit " in ln}
    assert {"experts.router.wd", "experts.router.w3", "experts.router.g",
            "attention.conv0_w", "attention.conv1_w", "attention.temp",
            "s1", "c4", "embed"} <= kinds
    if trace:
        # the program's counters are read on any device; the device
        # trace's metrics only on the chip
        assert last["metrics"]["moe.expert_imbalance"]["value"] >= 1
        assert "driver.device_wait_share" in last["metrics"]
        assert not {"cca.attend_roofline", "cca.device_share",
                    "moe.route_share"} & set(last["metrics"])
        # granite's four ``moe.*`` metrics do NOT list this cell:
        # ``test_scope_metrics.py`` holds their lists to granite's cell
        # alone, and only a ``benchmark`` PR may edit it (PERF.md 7).
        # The run is still held to no dropped assignment, by the
        # builder's verdict on the counters
        assert "moe.rows_overflow" not in last["metrics"]
        assert "'rows_overflow': 0" in proc.stdout
    else:
        assert set(last["metrics"]) == {"train_throughput", "setup_s"}
        assert last["metrics"]["train_throughput"]["value"] > 0


def test_the_control_goes_through_the_runners_own_comparison(tmp_path):
    """``tools/precision_reading.py`` takes this cell by its name: the
    product and the reference one precision below, each judged leaf by
    leaf against the configuration's limits."""
    proc = _run(tmp_path, "benchmarks/tools/precision_reading.py",
                "--workload", CELL, "--seeds", "2147483659", "--tiny")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    kinds = set(line["moved_by_kind"])
    assert {"attention.temp", "experts.router.w1", "s2", "embed"} <= kinds
    for side in ("product", "state_bf16"):
        assert isinstance(line[side]["correct"], bool)
        assert set(line[side]["worst_by_kind"]) == kinds


# ---------------------------------------------------------- the readers
# one traced step and a bit (1.25 whole executions): 10 ms busy; 2 ms in
# the blocked softmax, 1 ms in the mix, 1 ms in the router, 6 ms elsewhere
OBSERVED = {
    "peaks": {"bf16_flops_per_s": 100e12},
    "trace_steps": 1.25,
    "trace_device0": {
        "busy_s": 0.010,
        "op_self_s": {"fusion.1": 0.002, "fusion.2": 0.001,
                      "fusion.3": 0.001, "fusion.4": 0.006}},
    "scopes": {"fusion.1": "bigdl.cca.attend", "fusion.2": "bigdl.cca.mix",
               "fusion.3": "bigdl.moe.route"},
    "cca_counts": {"attend_flops_per_step": 4e10},
    "moe_counters": {"rows_by_expert_by_layer": [[10, 30], [20, 20]]},
}
# what the parent commit gives them: a traced step of a program that has
# no such scope and no such counter
PARENT = {"peaks": {"bf16_flops_per_s": 100e12}, "trace_steps": 1.25,
          "trace_device0": {"busy_s": 0.010,
                            "op_self_s": {"fusion.9": 0.010}},
          "scopes": {"fusion.9": "bigdl.moe.experts"},
          "moe_counters": {"rows_held": 5, "rows": 10}}


def _read(name, obs):
    return lib.load_module("layer_metrics", name).read(obs)


def test_the_new_readers_read_what_they_say():
    assert _read("cca.device_share", OBSERVED) == pytest.approx(0.3)
    assert _read("cca.mix_share", OBSERVED) == pytest.approx(0.1)
    assert _read("moe.route_share", OBSERVED) == pytest.approx(0.1)
    # 4e10 operations in 2 ms / 1.25 steps = 1.6 ms: 2.5e13 a second
    assert _read("cca.attend_roofline", OBSERVED) == pytest.approx(25.0)
    # the fullest expert of any layer, 30, over the mean of all, 20
    assert _read("moe.expert_imbalance", OBSERVED) == pytest.approx(1.5)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [PARENT, {}], ids=["parent", "nothing"])
def test_a_reader_with_nothing_to_read_returns_none(name, obs):
    assert _read(name, obs) is None


def test_the_counts_are_issue_35s():
    """0.91 GFLOP a token and 14.8 TFLOP a step, from the file's sizes;
    an assignment 75.5 MFLOP; the cut is the file's own."""
    cfg = lib.load_json("configs", "zaya1-8b-share2")
    builder = lib.load_module("builders", "zaya")
    per_token = builder.train_flops_per_record(cfg) / 8192
    assert per_token == pytest.approx(0.904e9, rel=2e-3)
    assert 2 * builder.train_flops_per_record(cfg) == \
        pytest.approx(14.8e12, rel=5e-3)
    assert builder.expert_flops_per_row(cfg) == 6 * 3 * 2048 * 2048
    whole = builder.whole_config(cfg)
    assert (whole["num_experts"], whole["num_attention_heads"],
            whole["num_key_value_heads"], whole["vocab_size"]) == \
        (16, 8, 2, 262272)
    assert builder.share(cfg) == (0, 2)
    assert builder.build_model(cfg).vocab_rows == (0, 32784)

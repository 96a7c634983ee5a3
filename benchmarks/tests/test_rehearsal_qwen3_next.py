"""The ``--tiny`` rehearsal of ``qwen3-next-80b-a3b-train-8k-1chip`` on
the CPU: the ``train_lm`` runner end to end at toy sizes with the
``qwen3_next`` builder and reference — the plain reference first (the
delta rule token by token), the short job whose parameters are compared
leaf by leaf, the measured job — the counters its readers take, the two
readers of the linear-attention layers, and the operation counts."""

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
CELL = "qwen3-next-80b-a3b-train-8k-1chip"
NEW = ["gdn.device_share", "gdn.scan_roofline"]


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
    # every kind of leaf the model has was compared: both mixers', the
    # router's, the shared expert's gate, the untied head
    kinds = {ln.split(":")[0].strip() for ln in lines if "(limit " in ln}
    assert {"mixer.in_proj_qkvz", "mixer.in_proj_ba", "mixer.conv_w",
            "mixer.A_log", "mixer.dt_bias", "mixer.norm_w", "mixer.wq",
            "mixer.q_norm", "experts.router", "shared_gate", "shared.w_in",
            "head", "embed", "final_norm"} <= kinds
    assert "'rows_overflow': 0" in proc.stdout
    if trace:
        # the program's counters are read on any device; the device
        # trace's metrics only on the chip
        assert last["metrics"]["moe.expert_imbalance"]["value"] >= 1
        assert "driver.device_wait_share" in last["metrics"]
        assert not {"gdn.device_share", "gdn.scan_roofline",
                    "moe.route_share"} & set(last["metrics"])
        assert "'gdn_counts'" in proc.stdout
    else:
        assert set(last["metrics"]) == {"train_throughput", "setup_s"}
        assert last["metrics"]["train_throughput"]["value"] > 0


def test_the_control_goes_through_the_runners_own_comparison(tmp_path):
    """``tools/precision_reading.py`` takes this cell by its name: the
    product and the reference one precision below (the recurrence's
    state in bf16 too), each judged leaf by leaf against the
    configuration's limits."""
    proc = _run(tmp_path, "benchmarks/tools/precision_reading.py",
                "--workload", CELL, "--seeds", "2147483659", "--tiny")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    kinds = set(line["moved_by_kind"])
    assert {"mixer.A_log", "mixer.in_proj_qkvz", "mixer.wq",
            "experts.w_in", "shared_gate"} <= kinds
    for side in ("product", "state_bf16"):
        assert isinstance(line[side]["correct"], bool)
        assert set(line[side]["worst_by_kind"]) == kinds


# ---------------------------------------------------------- the readers
# one traced step and a quarter (1.25 whole executions): 10 ms busy; 4 ms
# in the delta rule, 2 ms in the linear layers' projections, 4 elsewhere
OBSERVED = {
    "peaks": {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12},
    "trace_steps": 1.25,
    "trace_device0": {
        "busy_s": 0.010,
        "op_self_s": {"fusion.1": 0.004, "fusion.2": 0.002,
                      "fusion.3": 0.004}},
    "scopes": {"fusion.1": "bigdl.gdn.scan", "fusion.2": "bigdl.gdn.proj",
               "fusion.3": "bigdl.attention"},
    # 1 ms of operations, 2 ms of bytes: the bytes bound it
    "gdn_counts": {"scan_flops_per_step": 1e11,
                   "scan_bytes_per_step": 2e9},
}
# what the parent commit gives them: a traced step of a program that has
# no such scope and no such count
PARENT = {"peaks": OBSERVED["peaks"], "trace_steps": 1.25,
          "trace_device0": {"busy_s": 0.010,
                            "op_self_s": {"fusion.9": 0.010}},
          "scopes": {"fusion.9": "bigdl.moe.experts"},
          "moe_counters": {"rows_held": 5, "rows": 10}}


def _read(name, obs):
    return lib.load_module("layer_metrics", name).read(obs)


def test_the_new_readers_read_what_they_say():
    assert _read("gdn.device_share", OBSERVED) == pytest.approx(0.6)
    # the least time, 2 ms, over 4 ms / 1.25 steps = 3.2 ms a step
    assert _read("gdn.scan_roofline", OBSERVED) == pytest.approx(62.5)
    # operations bound it where they are the larger
    compute = dict(OBSERVED, gdn_counts={"scan_flops_per_step": 3e11,
                                         "scan_bytes_per_step": 2e9})
    assert _read("gdn.scan_roofline", compute) == pytest.approx(93.75)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [PARENT, {}], ids=["parent", "nothing"])
def test_a_reader_with_nothing_to_read_returns_none(name, obs):
    assert _read(name, obs) is None


def test_the_roofline_is_none_off_the_chip():
    assert _read("gdn.scan_roofline", dict(OBSERVED, peaks=None)) is None


def test_declared_in_benchmark_json():
    bench = lib.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_throughput"
    assert by_name["gdn.scan_roofline"]["unit"] == "%"
    train = {m["name"]: m for m in bench["end_to_end"]}["train_throughput"]
    assert CELL in train["workloads"]
    for name in ("model_step.mfu", "moe.route_share",
                 "moe.expert_imbalance"):
        assert CELL in by_name[name]["workloads"]


def test_the_counts_are_written_out_from_the_sizes():
    """Forward multiply-accumulates a token, from the file's sizes:
    a linear layer 2,048 x 12,352 + 4,096 x 2,048 of projections, 4 x
    8,192 of convolution, 32 heads x 3 x 128 x 128 of recurrence; the
    attention layer 2,048 x 36 x 256 + 4,096 x 2,048 of projections and
    2 x 16 x 256 x 4,096 of causal scores and values; an expert block
    2,048 x 512 of router, 10 x 64/512 x 3 x 2,048 x 512 of held experts,
    3 x 2,048 x 512 of shared expert and 2,048 of its gate; the head
    2,048 x 18,992.  Six operations a multiply-accumulate a record."""
    cfg = lib.load_json("configs", "qwen3-next-80b-a3b-ep8")
    builder = lib.load_module("builders", "qwen3_next")
    linear = 2048 * 12352 + 4096 * 2048 + 4 * 8192 + 32 * 3 * 128 * 128
    attention = 2048 * 36 * 256 + 4096 * 2048 + 2 * 16 * 256 * 4096
    experts = 2048 * 512 + 10 * 64 * 3 * 2048 * 512 // 512 \
        + 3 * 2048 * 512 + 2048
    macs = 3 * linear + attention + 4 * experts + 2048 * 18992
    assert macs == 238_100_480
    assert builder.train_flops_per_record(cfg) == 6 * macs * 8192
    assert builder.train_flops_per_record(cfg) == pytest.approx(11.70e12,
                                                                rel=1e-3)
    # the recurrence a step: 3 layers x 8,192 tokens x 32 heads x 3
    # products of 128 x 128, forward and backward; f32 words in a token,
    # q and k on each of the 16 key heads, v, g and beta on each of the
    # 32 value heads, and 128 out a value head, once forward and three
    # times backward
    counts = builder.gdn_counts(cfg)
    tokens = 3 * 8192
    assert counts["scan_flops_per_step"] == \
        6 * 3 * 128 * 128 * 32 * tokens == 231_928_233_984
    assert counts["scan_bytes_per_step"] == \
        4 * 4 * (16 * 256 + 32 * 130 + 32 * 128) * tokens == 4_857_004_032
    assert builder.expert_flops_per_row(cfg) == 6 * 3 * 2048 * 512
    whole = builder.whole_config(cfg)
    assert (whole["num_experts"], whole["vocab_size"],
            whole["num_hidden_layers"]) == (512, 151936, 4)
    assert builder.expert_share(cfg) == (0, 8)
    model = builder.build_model(cfg)
    assert model.vocab_rows == (0, 18992)
    assert model.layers[0].experts.n_rows(8192) == 15360
    assert [layer.kind for layer in model.layers] == \
        ["linear_attention"] * 3 + ["full_attention"]

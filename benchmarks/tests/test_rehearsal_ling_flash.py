"""The ``--tiny`` rehearsal of ``ling-3.0-flash-vl-train-8k-1chip`` on
the CPU: the ``train_lm`` runner end to end at toy sizes with the
``ling_flash`` builder and reference — the plain reference first (the
delta rule token by token, the expert bias carried by the step), the
short job whose parameters are compared leaf by leaf, the measured job
— the counters its readers take, the three readers of the new layers,
and the operation counts."""

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
CELL = "ling-3.0-flash-vl-train-8k-1chip"
NEW = ["kda.device_share", "kda.scan_roofline", "mla.device_share"]


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
    assert "the measured job repeats the first: True" in proc.stdout
    # every kind of leaf the model has was compared: both mixers', the
    # dense layer's MLP, the router's, the shared expert's, the head's
    kinds = {ln.split(":")[0].strip() for ln in lines if "(limit " in ln}
    assert {"mixer.in_proj", "mixer.conv_w", "mixer.A_log",
            "mixer.dt_bias", "mixer.norm_w", "mixer.out_proj", "mixer.wq",
            "mixer.wkv_a", "mixer.kv_norm", "mixer.wkv_b", "mixer.wg",
            "mixer.wo", "mlp.w_in", "experts.router", "experts.w_in",
            "shared.w_in", "head", "embed", "final_norm"} <= kinds
    assert "'rows_overflow': 0" in proc.stdout
    if trace:
        # the program's counters are read on any device; the device
        # trace's metrics only on the chip
        assert last["metrics"]["moe.expert_imbalance"]["value"] >= 1
        assert "driver.device_wait_share" in last["metrics"]
        assert not set(NEW + ["moe.route_share"]) & set(last["metrics"])
        assert "'kda_counts'" in proc.stdout
        assert "'bias_abs_max_by_layer'" in proc.stdout
    else:
        assert set(last["metrics"]) == {"train_throughput", "setup_s"}
        assert last["metrics"]["train_throughput"]["value"] > 0


def test_the_control_goes_through_the_runners_own_comparison(tmp_path):
    """``tools/precision_reading.py`` takes this cell by its name: the
    product and the reference one precision below, each judged leaf by
    leaf against the configuration's limits, the bias started anew for
    each seed."""
    proc = _run(tmp_path, "benchmarks/tools/precision_reading.py",
                "--workload", CELL, "--seeds", "2147483659,2147483660",
                "--tiny")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 2
    for line in lines:
        kinds = set(line["moved_by_kind"])
        assert {"mixer.A_log", "mixer.in_proj", "mixer.wkv_b",
                "experts.w_in", "mlp.w_out"} <= kinds
        for side in ("product", "state_bf16"):
            assert isinstance(line[side]["correct"], bool)
            assert set(line[side]["worst_by_kind"]) == kinds
        assert line["product"]["correct"]


# ---------------------------------------------------------- the readers
# one traced step and a quarter (1.25 whole executions): 10 ms busy; 4 ms
# in the delta rule, 2 ms in the KDA projections, 1 ms of latent
# attention, 3 elsewhere
OBSERVED = {
    "peaks": {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12},
    "trace_steps": 1.25,
    "trace_device0": {
        "busy_s": 0.010,
        "op_self_s": {"fusion.1": 0.004, "fusion.2": 0.002,
                      "fusion.3": 0.001, "fusion.4": 0.003}},
    "scopes": {"fusion.1": "bigdl.kda.scan", "fusion.2": "bigdl.kda.project",
               "fusion.3": "bigdl.mla.attend", "fusion.4": "bigdl.head"},
    # 1 ms of operations, 2 ms of bytes: the bytes bound it
    "kda_counts": {"scan_flops_per_step": 1e11,
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
    assert _read("kda.device_share", OBSERVED) == pytest.approx(0.6)
    assert _read("mla.device_share", OBSERVED) == pytest.approx(0.1)
    # the least time, 2 ms, over 4 ms / 1.25 steps = 3.2 ms a step
    assert _read("kda.scan_roofline", OBSERVED) == pytest.approx(62.5)
    # operations bound it where they are the larger
    compute = dict(OBSERVED, kda_counts={"scan_flops_per_step": 3e11,
                                         "scan_bytes_per_step": 2e9})
    assert _read("kda.scan_roofline", compute) == pytest.approx(93.75)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [PARENT, {}], ids=["parent", "nothing"])
def test_a_reader_with_nothing_to_read_returns_none(name, obs):
    assert _read(name, obs) is None


def test_the_roofline_is_none_off_the_chip():
    assert _read("kda.scan_roofline", dict(OBSERVED, peaks=None)) is None


def test_declared_in_benchmark_json():
    bench = lib.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_throughput"
        assert by_name[name]["layer"] == "model step"
    assert by_name["kda.scan_roofline"]["unit"] == "%"
    train = {m["name"]: m for m in bench["end_to_end"]}["train_throughput"]
    assert train["workloads"][-1] == CELL
    for name in ("model_step.mfu", "model_step.device_ms.train",
                 "moe.route_share", "moe.expert_imbalance",
                 "driver.dispatch_share", "input.block_stack_share"):
        assert by_name[name]["workloads"][-1] == CELL
    # the four lists a test pins to granite's cell are left as they are
    for name in ("moe.device_share", "moe.row_fill", "moe.rows_overflow",
                 "moe.experts_roofline"):
        assert CELL not in by_name[name]["workloads"]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"]) == (1, "train-steady-lm")


def test_the_counts_are_written_out_from_the_sizes():
    """Forward multiply-accumulates a token, from the file's sizes: a
    KDA layer 2,560 x 20,512 + 4,096 x 2,560 of projections, 4 x 12,288
    of convolution and 32 heads x 3.5 x 128 x 128 of recurrence (three
    products and the decay); the MLA layer 2,560 x 6,144 + 2,560 x 576 +
    512 x 8,192 + 2,560 x 32 + 4,096 x 2,560 of projections and 32 x
    (192 + 128) x 4,096 of causal scores and values; the dense layer's
    MLP 3 x 2,560 x 6,144; an expert block 2,560 x 512 of router, 8 x
    16/512 x 3 x 2,560 x 768 of held experts and 3 x 2,560 x 768 of
    shared expert; the head 2,560 x 19,648.  Six operations a
    multiply-accumulate a record."""
    cfg = lib.load_json("configs", "ling-3.0-flash-vl-ep32")
    builder = lib.load_module("builders", "ling_flash")
    kda = 2560 * 20512 + 4096 * 2560 + 4 * 12288 + 32 * 7 * 128 * 128 // 2
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 2560 * 32 \
        + 4096 * 2560 + 32 * 320 * 4096
    experts = 2560 * 512 + 8 * 16 * 3 * 2560 * 768 // 512 + 3 * 2560 * 768
    macs = 6 * kda + mla + 3 * 2560 * 6144 + 6 * experts + 2560 * 19648
    assert macs == 612_777_984
    assert builder.train_flops_per_record(cfg) == 6 * macs * 8192
    assert builder.train_flops_per_record(cfg) == pytest.approx(30.12e12,
                                                                rel=1e-3)
    # the recurrence a step: 6 layers x 8,192 tokens x 32 heads x (3
    # products of 128 x 128 and the decay), forward and backward; f32
    # words in a token, q, k and g a key channel, v, and beta a head,
    # and 128 out a head, once forward and three times backward
    counts = builder.kda_counts(cfg)
    tokens = 6 * 8192
    assert counts["scan_flops_per_step"] == \
        3 * 7 * 128 * 128 * 32 * tokens == 541_165_879_296
    assert counts["scan_bytes_per_step"] == \
        4 * 4 * 32 * (3 * 128 + 128 + 1 + 128) * tokens == 16_131_293_184
    assert builder.expert_flops_per_row(cfg) == 6 * 3 * 2560 * 768
    whole = builder.whole_config(cfg)
    assert (whole["num_experts"], whole["vocab_size"],
            whole["num_hidden_layers"]) == (512, 157184, 7)
    assert builder.expert_share(cfg) == (0, 32)
    model = builder.build_model(cfg)
    assert model.vocab_rows == (0, 19648)
    assert [(layer.kind, layer.dense) for layer in model.layers] == \
        [("kda", True)] + [("kda", False)] * 3 + [("mla", False)] \
        + [("kda", False)] * 2
    # R: 1.5 x the balanced 8,192 x 8 x 16 / 512 = 2,048
    assert model.layers[1].experts.n_rows(8192) == 3072
    assert model.layers[1].experts.held == (0, 16)

"""The metric arithmetic on hand-made inputs: rates, shares, mfu, the spread, the counts from HLO text, the
configurations' operation counts and the per-layer readers."""

import json
import os
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import hlo_count, lib  # noqa: E402


def test_rate_and_share():
    assert lib.rate(512, 0.25) == 2048
    with pytest.raises(lib.BenchFailure):
        lib.rate(1, 0)
    obs = {"phase_seconds": {"stage": 1.5}, "host_window_s": 6.0}
    assert lib.phase_share(obs, "stage") == 0.25
    assert lib.phase_share(obs, "dispatch") is None


def test_mfu_percent():
    # 24.5 GFLOP an image at 2,000 images/s on one 197 TFLOP/s chip
    assert lib.mfu_percent(24.5e9, 2000, 1, 197e12) == \
        pytest.approx(24.873, abs=1e-3)
    # four chips at the same total rate: a quarter of it
    assert lib.mfu_percent(24.5e9, 2000, 4, 197e12) == \
        pytest.approx(24.873 / 4, abs=1e-3)


def test_iqr_spread_is_statistics_quantiles():
    values = [100, 101, 99, 102, 98, 100.5]
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    assert lib.iqr_spread(values) == \
        pytest.approx((q3 - q1) / statistics.median(values))


def test_unknown_device_kind_is_an_error():
    assert lib.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(lib.BenchFailure):
        lib.peaks_for("TPU v99")


def test_metrics_for_and_with_tiny():
    bench = {"per_layer": [{"name": "a"},
                           {"name": "b", "workloads": ["x"]},
                           {"name": "c", "workloads": ["y"]}]}
    assert [m["name"] for m in lib.metrics_for(bench, "per_layer", "x")] \
        == ["a", "b"]
    d = {"model": {"w": 650, "n": 2}, "k": 8,
         "tiny": {"model": {"w": 32}, "k": 2}}
    assert lib.with_tiny(d, False) == {"model": {"w": 650, "n": 2}, "k": 8}
    assert lib.with_tiny(d, True) == {"model": {"w": 32, "n": 2}, "k": 2}


HLO = """
HloModule jit_block, entry_computation_layout={()->f32[]}

%fused_kernel (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  ROOT %custom-call.7 = f32[8,128]{1,0} custom-call(%p0), custom_call_target="tpu_custom_call"
}

ENTRY %main (a: f32[1024,256]) -> f32[1024,256] {
  %a = f32[1024,256]{1,0} parameter(0)
  %all-reduce.1 = f32[1024,256]{1,0} all-reduce(%a), replica_groups={{0,1,2,3}}
  %all-gather-start.2 = (f32[256,256]{1,0}, f32[1024,256]{1,0}) all-gather-start(%a), dimensions={0}
  %all-gather-done.2 = f32[1024,256]{1,0} all-gather-done(%all-gather-start.2)
  %reduce-scatter.3 = bf16[256,256]{1,0} reduce-scatter(bf16[1024,256]{1,0} %b), dimensions={0}
  %custom-call.9 = f32[8,128]{1,0} custom-call(%a), custom_call_target="Sharding"
  %fusion.4 = f32[8,128]{1,0} fusion(%a), kind=kCustom, calls=%fused_kernel
  ROOT %copy.5 = f32[1024,256]{1,0} copy(%all-gather-done.2)
}
"""


def test_collective_wire_bytes():
    wire = hlo_count.collective_wire_bytes(HLO)
    assert wire["all-reduce"] == 1024 * 256 * 4        # result bytes
    assert wire["all-gather"] == 1024 * 256 * 4        # largest of the tuple
    assert wire["reduce-scatter"] == 1024 * 256 * 2    # operand bytes
    assert wire["total"] == sum(v for k, v in wire.items() if k != "total")


def test_op_names_find_collectives_and_pallas_calls():
    names = hlo_count.op_names(HLO)
    assert names["collective"] == ["all-gather-done.2", "all-gather-start.2",
                                   "all-reduce.1", "reduce-scatter.3"]
    # the kernel, the fusion that wraps it, and not the Sharding call
    assert names["pallas"] == ["custom-call.7", "fusion.4"]


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet50_counts_the_published_operations():
    builder = lib.load_module("builders", "resnet50")
    cfg = _config("resnet50-imagenet")
    # 4.09 GMAC an image is the published count of this network
    assert builder.conv_macs(cfg) == pytest.approx(4.09e9, rel=2e-3)
    assert builder.train_flops_per_record(cfg) == \
        6 * builder.conv_macs(cfg)


def test_ptb_medium_counts_from_its_sizes():
    builder = lib.load_module("builders", "ptb_lstm")
    cfg = _config("ptb-medium-lstm")
    per_token = 2 * (650 + 650) * 4 * 650 + 650 * 10000
    assert builder.train_flops_per_record(cfg) == 6 * per_token * 35


def test_every_metric_and_cell_of_benchmark_json_has_its_files():
    bench = lib.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(lib.load_module("layer_metrics", m["name"]).read)
    ends = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in ends for m in bench["per_layer"])
    for cell in bench["workloads"]:
        cfg = lib.load_json("configs", cell["config"])
        traffic = lib.load_json("traffic", cell["traffic"])
        lib.load_module("builders", cfg["builder"])
        assert callable(lib.load_module("runners", traffic["runner"]).run)


def test_layer_readers_on_hand_made_observations():
    obs = {
        "chips": 1, "host_window_s": 10.0, "host_records_per_s": 2000.0,
        "phase_seconds": {"dispatch": 0.5, "device_wait": 7.0, "stage": 2.0},
        "flops_per_record": 24.5e9,
        "peaks": {"bf16_flops_per_s": 197e12},
        "facts": {"wire_bytes": {"all-reduce": 100, "total": 100},
                  "op_names": {"pallas": ["custom-call.7"]}},
        "trace_device0": {"busy_s": 2.0, "window_s": 2.5,
                          "collective_s": 0.25, "pallas_s": 0.5},
        "trace_steps": 20,
    }
    want = {"driver.dispatch_share": 0.05, "driver.device_wait_share": 0.7,
            "driver.stage_share": 0.2, "grad_sync.collective_share": 0.1,
            "grad_sync.wire_bytes_per_step": 100,
            "model_step.device_ms.train": 100.0,
            "model_step.mfu": lib.mfu_percent(24.5e9, 2000.0, 1, 197e12),
            "kernel.pallas_share": 0.25}
    for name, value in want.items():
        got = lib.load_module("layer_metrics", name).read(obs)
        assert got == pytest.approx(value), name
    # a reader that finds nothing to read returns nothing
    empty = {"host_window_s": 1.0, "phase_seconds": {}, "facts": {},
             "trace_device0": {}, "trace_steps": 0, "peaks": None}
    for name in want:
        assert lib.load_module("layer_metrics", name).read(empty) is None

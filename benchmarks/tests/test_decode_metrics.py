"""The decode cell's per-layer readers and the counts behind them."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import lib, trace_programs  # noqa: E402

READERS = ("decode.step_device_ms", "decode.prefill_device_ms",
           "decode.prefill_device_share", "decode.slot_occupancy",
           "decode.step_roofline", "decode.mfu", "decode.itl_p50_ms")
CFG = {"n_embd": 8, "n_inner": None, "vocab_size": 100, "n_layer": 2}
OBSERVED = {
    "decode_programs": {
        "step": {"count": 100, "seconds": 2.0, "median_s": 0.020},
        "prefill": {"count": 4, "seconds": 0.16, "median_s": 0.04},
        "splice": {"count": 4, "seconds": 0.04, "median_s": 0.01}},
    "device_busy_s": 2.5,
    "decode_counters": {"slots": 4, "steps": 1000,
                        "active_slot_steps": 3900, "admissions": 50},
    "needed_bytes_per_step": 8.19e9,
    "itl_ms": {"p50": 51.5, "gaps": 1200},
    "host_window_s": 10.0, "host_needed_flops": 197e12, "chips": 1,
    "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def _read(name, observed):
    return lib.load_module("layer_metrics", name).read(observed)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_returns_none(name):
    assert _read(name, {}) is None
    assert _read(name, {"decode_programs": {}, "peaks": None,
                        "decode_counters": None}) is None


@pytest.mark.parametrize("name,want", [
    ("decode.step_device_ms", 20.0),
    ("decode.prefill_device_ms", 50.0),        # (0.16 + 0.04) / 4
    ("decode.prefill_device_share", 0.08),     # 0.2 of 2.5
    ("decode.slot_occupancy", 0.975),
    ("decode.step_roofline", 50.0),            # 8.19e9 / (0.02 x 819e9)
    ("decode.mfu", 10.0), ("decode.itl_p50_ms", 51.5)])
def test_reader_on_hand_worked_observations(name, want):
    assert _read(name, OBSERVED) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("gaps,want", [
    ([0.0, 1e-4, 2e-4], (True, True)),         # rounding
    ([0.0] * 99 + [0.07], (False, False)),     # mean 7e-4, widest 0.07
    ([1e-3] * 4, (False, True)),               # a lower precision
    ([0.0] * 999 + [0.07], (True, False)),     # one token plainly wrong
    ([], (False, False))])                     # nothing compared
def test_gap_checks_hold_both_limits(gaps, want):
    import numpy as np
    runner = lib.load_module("runners", "decode")
    limits = {"mean_gap_limit": 3.0e-4, "gap_limit": 0.06}
    checks = runner.gap_checks(np.asarray(gaps, np.float32), limits)
    assert [c[0] for c in checks] == ["mean_gap", "widest_gap"]
    assert [c[2] for c in checks] == [3.0e-4, 0.06]
    assert tuple(c[3] for c in checks) == want


def test_held_cell_is_not_in_benchmark_json_but_found_when_asked():
    cell = "gpt2-xl-decode-saturated-1chip"
    plain, held = lib.load_benchmark(), lib.load_benchmark(held=True)
    assert cell not in [w["name"] for w in plain["workloads"]]
    assert not [m for m in plain["per_layer"] + plain["end_to_end"]
                if cell in m.get("workloads", [])]
    assert cell in [w["name"] for w in held["workloads"]]
    assert [m["name"] for m in lib.metrics_for(held, "end_to_end", cell)] \
        == ["setup_s", "decode_throughput", "itl_p99_ms"]
    assert [m["name"] for m in lib.metrics_for(held, "per_layer", cell)] \
        == list(READERS)
    # what is held changes nothing for the cells that are in
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert held[key][:len(plain[key])] == plain[key]


def test_roofline_and_mfu_need_the_peaks():
    off_chip = dict(OBSERVED, peaks=None)
    assert _read("decode.step_roofline", off_chip) is None
    assert _read("decode.mfu", off_chip) is None


def test_needed_bytes_on_hand_worked_sizes():
    b = lib.load_module("builders", "transformer_lm")
    # d 8, f 32, v 100, 2 layers: matrices 2 x (4 x 64 + 2 x 256) + 800
    assert b.matmul_parameters(CFG) == 2 * (256 + 512) + 800 == 2336
    vectors = 2 * (4 * 8 + 32 + 8 + 4 * 8) + 2 * 8 + 100      # 324
    kv_position = 2 * 2 * 8 * 4                               # 128 bytes
    want = 4 * (2336 + vectors) + 3 * 2 * 8 * 4 \
        + (50 + 3) * kv_position + 3 * 100 * 4
    assert b.needed_bytes_per_step(CFG, 3, 50) == want == 18816
    # a position more in use is one position's keys and values more
    assert b.needed_bytes_per_step(CFG, 3, 51) - want == 128


def test_flops_on_hand_worked_sizes():
    b = lib.load_module("builders", "transformer_lm")
    assert b.flops_per_token(CFG, 10) == 2 * 2336 + 4 * 2 * 8 * 10
    # a prompt of 3: the layers' matrices thrice, the head once, and
    # attention over 1 + 2 + 3 positions
    assert b.prompt_flops(CFG, 3) == 2 * 1536 * 3 + 2 * 800 \
        + 4 * 2 * 8 * 6


def test_gpt2_xl_counts():
    b = lib.load_module("builders", "transformer_lm")
    ref = lib.load_module("references", "transformer_lm")
    cfg = lib.load_json("configs", "gpt2-xl")
    assert ref.parameter_count(cfg) == 1638072657
    assert cfg["reduced"] == [] and cfg["n_layer"] == 48
    # one slot's strips: 2 x 48 x 25 x 1,024 x 64 x 4
    assert 2 * 48 * 1600 * 4 * 1024 == 629145600
    full = b.needed_bytes_per_step(cfg, 4, 4 * 1023)
    # every parameter but the two embedding tables: 6.23 of 6.55 GB
    assert 6.22e9 < b.needed_bytes_per_step(cfg, 4, 0) < 6.24e9
    assert full - b.needed_bytes_per_step(cfg, 4, 0) == 4 * 1023 * 614400


KINDS = {"step": ["_step_fn"], "prefill": ["_prefill_fn"],
         "splice": ["_splice_fn"]}
EVENTS = [(0, 10, "jit__step_fn(1)"), (15, 25, "jit__step_fn(1)"),
          (25, 65, "jit__prefill_fn(7)"), (70, 80, "jit__splice_fn(3)"),
          (180, 190, "jit__step_fn(1)"), (190, 195, "jit_convert(9)")]


def test_programs_by_kind_and_named_gaps():
    by = trace_programs.by_kind(EVENTS, KINDS)
    assert by["step"]["count"] == 3 and by["prefill"]["count"] == 1
    assert by["step"]["seconds"] == pytest.approx(30e-9)
    assert by["step"]["median_s"] == pytest.approx(10e-9)
    assert by["other"]["count"] == 1
    gaps = trace_programs.named_gaps(EVENTS, KINDS, 2)
    assert gaps[0] == ("after_splice.before_step", pytest.approx(100e-9))
    assert gaps[1][0] in ("after_step.before_step",
                          "after_prefill.before_splice")
    totals = trace_programs.gap_totals(EVENTS, KINDS)
    assert totals["after_step.before_step"]["count"] == 1
    assert sum(t["count"] for t in totals.values()) == 3

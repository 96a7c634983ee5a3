"""The decode service's readers from inside (PR 37): each against a
``stats()["decode"]`` dictionary worked by hand, ``None`` without
``observed["service"]``, against a live service's own dictionary at the
tiny size, and the held file that names them."""

import functools
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import lib  # noqa: E402

CELL = "gpt2-xl-decode-saturated-1chip"
HELD = os.path.join(lib.HERE, "held", "gpt2-xl-decode-service.json")
READERS = ("decode.host_step_ms", "decode.launch_ms", "decode.admit_share",
           "decode.queue_wait_p95_ms", "decode.kv_fill",
           "decode.prefill_pad_share", "decode.fetch_bytes_per_admission")
SPAN_READERS = READERS[:3]      # need a tracer in the service

# the shape of DecodeService.stats()["decode"] with a tracer held: 1,000
# steps of 51 ms of which the device is waited for 47, 16 admissions
SERVICE = {
    "slots": 4, "steps": 1000, "admissions": 16, "tokens_generated": 3900,
    "step_ms": {"count": 1000, "p50": 51.0, "p95": 52.0, "p99": 130.0},
    "queue_wait_ms": {"count": 16, "mean": 9000.0, "p50": 8000.0,
                      "p95": 15000.0, "p99": 16000.0},
    "kv_positions_used": 1_433_600, "kv_positions_reserved": 4_096_000,
    "prefill_tokens": 4800, "prefill_tokens_padded": 6400,
    "first_fetch_bytes": 16 * 80_000_000,
    "expired_before_admit": 0, "expired_mid_decode": 0,
    "spans": {
        "idle": {"cat": "decode_idle", "seconds": 30.0, "spans": 2,
                 "median_ms": 15000.0},
        "schedule": {"cat": "decode_schedule", "seconds": 0.5,
                     "spans": 1001, "median_ms": 0.004},
        "admit": {"cat": "decode_admit", "seconds": 12.0, "spans": 16,
                  "median_ms": 700.0},
        "step": {"cat": "decode_step", "seconds": 51.5, "spans": 1000,
                 "median_ms": 51.0},
        "dispatch": {"cat": "decode_launch", "seconds": 1.7, "spans": 1000,
                     "median_ms": 1.67},
        "prefill_launch": {"cat": "decode_launch", "seconds": 0.04,
                           "spans": 16, "median_ms": 2.5},
        "device_wait": {"cat": "decode_device_wait", "seconds": 47.0,
                        "spans": 1000, "median_ms": 47.0},
        "step_fetch": {"cat": "decode_fetch", "seconds": 0.72,
                       "spans": 1000, "median_ms": 0.72},
    },
    "host_step_ms": 4.0, "loop_unspanned_share": 0.001,
    "trace_dropped_events": 0,
}
WANT = {
    "decode.host_step_ms": 4.0, "decode.launch_ms": 1.67,
    "decode.admit_share": 12.0 / 64.0,          # idle left out
    "decode.queue_wait_p95_ms": 15000.0,
    "decode.kv_fill": 0.35, "decode.prefill_pad_share": 0.25,
    "decode.fetch_bytes_per_admission": 80_000_000.0,
}


def _read(name, observed):
    return lib.load_module("layer_metrics", name).read(observed)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_worked_stats(name):
    assert _read(name, {"service": SERVICE}) == pytest.approx(
        WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_none_without_the_service(name):
    # what runners/decode.py hands its readers today has no "service"
    assert _read(name, {}) is None
    assert _read(name, {"service": None, "decode_counters": {"steps": 3}}) \
        is None
    # a service that has admitted and stepped nothing yet
    assert _read(name, {"service": {
        "steps": 0, "admissions": 0, "kv_positions_used": 0,
        "kv_positions_reserved": 0, "prefill_tokens": 0,
        "prefill_tokens_padded": 0, "first_fetch_bytes": 0,
        "queue_wait_ms": {"count": 0, "mean": 0.0}}}) is None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_none_for_a_service_without_a_tracer(name):
    untraced = {k: v for k, v in SERVICE.items()
                if k not in ("spans", "host_step_ms",
                             "loop_unspanned_share",
                             "trace_dropped_events")}
    assert _read(name, {"service": untraced}) is None
    for counter in set(READERS) - set(SPAN_READERS):
        assert _read(counter, {"service": untraced}) == pytest.approx(
            WANT[counter])


@functools.lru_cache(maxsize=None)
def _live_stats():
    """A real ``DecodeService`` at the tiny size, traced, after three
    answers: what ``svc.stats()["decode"]`` really holds.  (No fixture:
    ``tests/test_benchmark_guards.py`` hands tier-1 this module's test
    functions and nothing else of it.)"""
    import numpy as np
    from bigdl_tpu.models.transformer import transformer_lm
    from bigdl_tpu.serving.decode import DecodeService
    from bigdl_tpu.telemetry.tracer import Tracer
    lm = transformer_lm(vocab_size=64, embed_dim=32, num_heads=4,
                        num_layers=2, max_len=64).initialize(0)
    rng = np.random.default_rng(3)
    with DecodeService(lm, slots=2, max_seq_len=64,
                       tracer=Tracer()) as svc:
        futs = [svc.submit(rng.integers(0, 64, n).astype(np.int32),
                           max_new_tokens=g)
                for n, g in ((5, 4), (12, 6), (20, 3))]
        for f in futs:
            f.result(timeout=120)
        return svc.stats()["decode"]


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_live_services_stats(name):
    live_stats = _live_stats()
    value = _read(name, {"service": live_stats})
    assert isinstance(value, float) and value >= 0.0
    json.dumps(live_stats)          # the runner prints it; plain numbers
    if name == "decode.prefill_pad_share":
        assert value == pytest.approx(1.0 - 37 / (8 + 16 + 32))
    if name == "decode.fetch_bytes_per_admission":
        assert value == (8 + 16 + 32) * 64 * 4 / 3
    if name == "decode.kv_fill":
        # (5+6+7) + (12+..+16) + (20+21) positions over 2 x 64 a step
        assert value == pytest.approx(
            129 / (live_stats["steps"] * 2 * 64))
    if name in ("decode.admit_share", "decode.kv_fill"):
        assert value < 1.0
    if name == "decode.host_step_ms":
        assert value <= live_stats["spans"]["step"]["median_ms"]


def test_hand_worked_stats_have_the_live_services_shape():
    live = _live_stats()
    assert set(SERVICE) <= set(live)
    assert set(SERVICE["spans"]) <= set(live["spans"]) | {"idle"}
    for name, row in SERVICE["spans"].items():
        assert set(row) == set(live["spans"]["step"])
    assert "fetch_bytes" not in live        # one counter: first_fetch_bytes


def test_held_file_names_readers_that_exist():
    with open(HELD) as f:
        held = json.load(f)
    entries = held["per_layer_waiting"]
    assert [m["name"] for m in entries] == list(READERS)
    keys = {"name", "unit", "better", "source", "layer", "moves",
            "workloads"}
    for m in entries:
        assert set(m) == keys
        assert os.path.isfile(os.path.join(lib.HERE, "layer_metrics",
                                           m["name"] + ".py"))
        assert callable(lib.load_module("layer_metrics", m["name"]).read)
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["moves"]) == ("decode service",
                                            "decode_throughput")
        assert m["better"] in ("lower", "higher")
        assert m["source"] == ("program_span" if m["name"] in SPAN_READERS
                               else "program_counter")


def test_the_waiting_list_changes_nothing_that_is_merged():
    # the entries wait under a key load_benchmark does not merge: the
    # held cell's per-layer list is what it was, BENCHMARK.json's too
    held = lib.load_benchmark(held=True)
    names = [m["name"] for m in lib.metrics_for(held, "per_layer", CELL)]
    assert names and not set(names) & set(READERS)
    assert CELL not in [w["name"]
                        for w in lib.load_benchmark()["workloads"]]

"""The span reduction: its interval arithmetic on hand-made inputs, and
the whole of it on a small trace recorded on the chip
(``tiny_spans.xplane.pb``: ``record_spans.py``'s tiny ``LocalOptimizer``
run under telemetry on a TPU v5 lite, PR 24)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import host_spans as hs  # noqa: E402

TRACE = os.path.join(HERE, "tiny_spans.xplane.pb")
MS = 1_000_000  # ns

# one block of the driver's thread, as the tracer nests it
BLOCK = [
    (0 * MS, 100 * MS, "stage_next", "stage_next"),
    (0 * MS, 2 * MS, "plan", "plan"),
    (2 * MS, 60 * MS, "stage", "host_stack"),
    (5 * MS, 35 * MS, "batch_pull", "batch_pull"),
    (40 * MS, 58 * MS, "block_stack", "block_stack"),
    (60 * MS, 70 * MS, "stage", "h2d_stage"),
    (75 * MS, 95 * MS, "step_args", "step_args"),
    (110 * MS, 120 * MS, "dispatch", "dispatch"),
    (125 * MS, 200 * MS, "device_wait", "device_wait"),
]


def test_parse():
    assert hs.parse("bigdl:stage:host_stack") == ("stage", "host_stack")
    assert hs.parse("bigdl::bare") == ("uncategorized", "bare")
    assert hs.parse("bigdl:serving:a:b") == ("serving", "a:b")


def test_innermost_segments_flatten_the_nesting():
    segs = hs.innermost_segments(BLOCK)
    assert segs == [
        (0 * MS, 2 * MS, "plan"),
        (2 * MS, 5 * MS, "host_stack"),
        (5 * MS, 35 * MS, "batch_pull"),
        (35 * MS, 40 * MS, "host_stack"),
        (40 * MS, 58 * MS, "block_stack"),
        (58 * MS, 60 * MS, "host_stack"),
        (60 * MS, 70 * MS, "h2d_stage"),
        (70 * MS, 75 * MS, "stage_next"),
        (75 * MS, 95 * MS, "step_args"),
        (95 * MS, 100 * MS, "stage_next"),
        (110 * MS, 120 * MS, "dispatch"),
        (125 * MS, 200 * MS, "device_wait"),
    ]
    # disjoint, in order, and as long as the union of the spans
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    assert sum(e - s for s, e, _ in segs) == (100 + 10 + 75) * MS


def test_attribute_sums_to_the_gap():
    segs = hs.innermost_segments(BLOCK)
    # a gap that lies inside batch_pull is batch_pull's, whole
    assert hs.attribute(10 * MS, 30 * MS, segs) == {"batch_pull": 0.020}
    # one that runs from the K-axis copy over the hole before dispatch
    got = hs.attribute(50 * MS, 115 * MS, segs)
    assert got == pytest.approx({
        "block_stack": 0.008, "host_stack": 0.002, "h2d_stage": 0.010,
        "stage_next": 0.010, "step_args": 0.020, "dispatch": 0.005,
        hs.UNSPANNED: 0.010})
    assert sum(got.values()) == pytest.approx(0.065)
    # no span at all: every second is unspanned
    assert hs.attribute(0, 5 * MS, []) == {hs.UNSPANNED: 0.005}


def test_category_seconds_and_driver_thread():
    other = [(0, 50 * MS, "batch_assemble", "assemble")]
    threads = [{"plane": "/host:CPU", "line": "worker", "events": other},
               {"plane": "/host:CPU", "line": "main", "events": BLOCK}]
    assert hs.driver_thread(threads)["line"] == "main"
    assert hs.driver_thread(threads[:1]) is None
    cats = hs.category_seconds(threads)
    assert cats["stage"] == pytest.approx(
        {"seconds": 0.068, "self_seconds": 0.020, "spans": 2})
    assert cats["stage_next"]["self_seconds"] == pytest.approx(0.010)
    assert cats["batch_pull"]["self_seconds"] == pytest.approx(0.030)
    assert cats["batch_assemble"]["seconds"] == pytest.approx(0.050)
    # self seconds of one thread add up to the union of its spans
    assert sum(r["self_seconds"] for c, r in cats.items()
               if c != "batch_assemble") == pytest.approx(0.185)


@pytest.mark.skipif(not os.path.isfile(TRACE), reason="no recorded trace")
def test_recorded_chip_trace():
    assert os.path.getsize(TRACE) < 100_000
    red = hs.reduce_file(TRACE)
    # the driver's thread is found, with every category the driver emits
    assert red["driver_line"] is not None
    assert {"stage_next", "plan", "stage", "batch_pull", "block_stack",
            "step_args", "dispatch", "device_wait", "replay"} <= \
        set(red["categories"])
    cats = red["categories"]
    assert cats["stage_next"]["seconds"] >= cats["stage"]["seconds"] \
        >= cats["batch_pull"]["seconds"]
    for row in cats.values():
        assert 0 <= row["self_seconds"] <= row["seconds"] + 1e-9
    # the spans lie on the device's clock: ten gaps, each one's parts
    # summing to its length, and nearly all of it under a named span
    assert len(red["gaps"]) == hs.N_GAPS
    for gap in red["gaps"]:
        assert sum(gap["by_span"].values()) == \
            pytest.approx(gap["seconds"], rel=1e-9)
    assert red["gap_named_share"] > 0.9
    # the mini-batch held back for 30 ms: the longest gap, in batch_pull
    longest = red["gaps"][0]
    assert longest["seconds"] > 0.025
    top = max(longest["by_span"], key=longest["by_span"].get)
    assert top == "batch_pull"
    assert longest["by_span"]["batch_pull"] > 0.9 * longest["seconds"]

"""The xplane reduction: its interval arithmetic on hand-made inputs, and
the whole of it on a small trace recorded on the chip
(``tiny_step.xplane.pb``: five executions of ``record_trace.py``'s
program on a TPU v5 lite, PR 23)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import trace_reduce as tr  # noqa: E402

TRACE = os.path.join(HERE, "tiny_step.xplane.pb")


def test_union_seconds_merges_overlaps_and_nesting():
    ns = 1_000_000_000
    assert tr.union_seconds([]) == 0
    assert tr.union_seconds([(0, ns), (2 * ns, 3 * ns)]) == 2.0
    assert tr.union_seconds([(0, 2 * ns), (ns, 3 * ns)]) == 3.0
    assert tr.union_seconds([(0, 4 * ns), (ns, 2 * ns)]) == 4.0  # nested


def test_gaps_longest_first():
    got = tr.gaps([(0, 10), (15, 20), (50, 60), (12, 13)])
    assert got == [(30, 20), (2, 13), (2, 10)]  # ties: later first
    assert tr.gaps([(5, 10)], lo=0, hi=30) == [(20, 10), (5, 0)]


def test_self_times_take_children_off_the_parent():
    ns = 1_000_000_000
    events = [(0, 10 * ns, "while.1"),        # parent of the next two
              (1 * ns, 3 * ns, "fusion.2"),
              (4 * ns, 9 * ns, "fusion.2"),
              (12 * ns, 13 * ns, "copy.3")]
    got = tr.self_times(events)
    assert got == {"while.1": 3.0, "fusion.2": 7.0, "copy.3": 1.0}
    assert sum(got.values()) == tr.union_seconds(
        [(s, e) for s, e, _ in events])


def test_names():
    assert tr.base_name("%fusion.12") == "fusion.12"
    assert tr.base_name("%while.7 = (s32[], f32[8]{0}) while(%t)") == "while.7"
    assert tr.label("%copy.9 = f32[128,128]{1,0:T(8,128)} copy(f32[128,128] %x)") \
        == "copy.9 copy f32[128,128]{1,0:T(8,128)}"
    assert tr.COLLECTIVE_RE.match("all-gather-done.2")
    assert not tr.COLLECTIVE_RE.match("fusion.2")


@pytest.mark.skipif(not os.path.isfile(TRACE), reason="no recorded trace")
def test_recorded_chip_trace_reduces():
    assert os.path.getsize(TRACE) < 1_000_000
    red = tr.reduce_file(TRACE)
    assert len(red["devices"]) == 1
    dev = red["devices"][0]
    assert dev["plane"] == "/device:TPU:0"
    # five executions of one program, the loop's body nested in it
    (module, stats), = dev["modules"].items()
    assert module.startswith("jit_tiny_step") and stats["count"] == 5
    assert stats["whole_executions"] == pytest.approx(5, rel=0.2)
    assert stats["median_s"] * stats["whole_executions"] == \
        pytest.approx(stats["seconds"])
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert dev["busy_s"] == pytest.approx(stats["seconds"], rel=0.2)
    assert sum(dev["op_self_s"].values()) == \
        pytest.approx(dev["busy_s"], rel=1e-6)
    assert any(n.startswith("while") for n in dev["op_self_s"])
    # the 20 ms sleep after the third execution is the longest gap
    length, _at = dev["idle_gaps"][0]
    assert length > 0.015
    assert dev["collective_s"] == 0 and red["busy_s"] == dev["busy_s"]

"""``input.assemble_share`` (PR 26) on hand-made ``observed`` dicts: the
assembler thread's ``batch_assemble`` seconds over the host part of the
window, and ``None`` (so that the line leaves the metric out) where the
program records no such span."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import lib  # noqa: E402

NAME = "input.assemble_share"


def read(obs):
    return lib.load_module("layer_metrics", NAME).read(obs)


def test_reads_its_share():
    # the assembler worked 0.6 s of a 1 s window, beside a driver whose
    # own spans fill the window: the share is of the window, not of them
    obs = {"host_window_s": 1.0,
           "phase_seconds": {"stage_next": 0.7, "batch_pull": 0.5,
                             "block_stack": 0.0, "dispatch": 0.3,
                             "batch_assemble": 0.6}}
    assert read(obs) == pytest.approx(0.6)
    assert read(dict(obs, host_window_s=2.0)) == pytest.approx(0.3)


@pytest.mark.parametrize("obs", [
    # the parent commit, or an inline assembler: every span but this one
    {"host_window_s": 1.0,
     "phase_seconds": {"stage_next": 0.7, "batch_pull": 0.5,
                       "block_stack": 0.1, "dispatch": 0.3}},
    {"host_window_s": 1.0, "phase_seconds": {}},
    {"host_window_s": 1.0, "phase_seconds": None}, {"host_window_s": 1.0}],
    ids=["parent", "empty", "none", "absent"])
def test_none_without_the_span(obs):
    assert read(obs) is None


def test_declared_in_benchmark_json():
    m = {m["name"]: m for m in lib.load_benchmark()["per_layer"]}[NAME]
    assert m == {"name": NAME, "unit": "share", "better": "lower",
                 "source": "program_span", "layer": "input pipeline",
                 "moves": "train_throughput",
                 # the cells whose batches MTSampleToMiniBatch assembles
                 "workloads": ["resnet50-train-4chip",
                               "resnet50-train-1chip"]}

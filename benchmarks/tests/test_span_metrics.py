"""The four per-layer readers of PR 24 on hand-made ``observed`` dicts:
each a share of the host part of the window, and ``None`` (so that the
line leaves the metric out) where the program has no such span."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import lib  # noqa: E402

# one second of window: a block's staging 0.5 (its children 0.45),
# dispatch 0.01, the loss fetch 0.3, replay 0.15; 0.04 in no span
OBSERVED = {
    "host_window_s": 1.0,
    "phase_seconds": {
        "stage_next": 0.5, "plan": 0.01, "stage": 0.24,
        "batch_pull": 0.125, "block_stack": 0.0625, "step_args": 0.2,
        "dispatch": 0.01, "device_wait": 0.3, "replay": 0.15,
        "trigger": 0.1},
}
# what the parent commit's tracer gives: no new category
PARENT = {"host_window_s": 1.0,
          "phase_seconds": {"stage": 0.24, "dispatch": 0.01,
                            "device_wait": 0.3, "replay": 0.15}}


def read(name, obs):
    return lib.load_module("layer_metrics", name).read(obs)


@pytest.mark.parametrize("name, want", [
    ("driver.unspanned_share", 0.04),
    ("driver.step_args_share", 0.2),
    ("input.batch_pull_share", 0.125),
    ("input.block_stack_share", 0.0625),
])
def test_reads_its_share(name, want):
    assert read(name, OBSERVED) == pytest.approx(want)
    half = dict(OBSERVED, host_window_s=2.0)
    if name != "driver.unspanned_share":
        assert read(name, half) == pytest.approx(want / 2)


@pytest.mark.parametrize("name", [
    "driver.unspanned_share", "driver.step_args_share",
    "input.batch_pull_share", "input.block_stack_share"])
@pytest.mark.parametrize("obs", [
    PARENT, {"host_window_s": 1.0, "phase_seconds": {}},
    {"host_window_s": 1.0, "phase_seconds": None}, {"host_window_s": 1.0}],
    ids=["parent", "empty", "none", "absent"])
def test_none_without_the_span(name, obs):
    assert read(name, obs) is None


def test_unspanned_counts_only_top_level_and_floors_at_zero():
    # children are not added to their parents: the same numbers with the
    # children left out read the same
    tops = {k: v for k, v in OBSERVED["phase_seconds"].items()
            if k in ("stage_next", "dispatch", "device_wait", "replay")}
    assert read("driver.unspanned_share",
                {"host_window_s": 1.0, "phase_seconds": tops}) == \
        pytest.approx(0.04)
    # a span that straddles the window's end counts whole where it
    # starts: the sum can pass the window, the share cannot pass 0
    over = dict(tops, device_wait=0.5)
    assert read("driver.unspanned_share",
                {"host_window_s": 1.0, "phase_seconds": over}) == 0.0
    # a top-level category that never occurred counts as zero seconds
    assert read("driver.unspanned_share",
                {"host_window_s": 1.0,
                 "phase_seconds": {"stage_next": 0.25}}) == \
        pytest.approx(0.75)


def test_declared_in_benchmark_json():
    bench = lib.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # every cell that trains: a cell that serves has no training driver
    # to span (PR 34)
    cells = {m["name"]: m for m in bench["end_to_end"]}[
        "train_throughput"]["workloads"]
    for name, layer in [("driver.unspanned_share", "training driver"),
                        ("driver.step_args_share", "training driver"),
                        ("input.batch_pull_share", "input pipeline"),
                        ("input.block_stack_share", "input pipeline")]:
        m = by_name[name]
        assert (m["layer"], m["source"], m["moves"], m["better"],
                m["unit"]) == (layer, "program_span", "train_throughput",
                               "lower", "share")
        assert m["workloads"] == cells


def test_tiny_rehearsal_prints_the_four(tmp_path):
    """The traced ``--tiny`` rehearsal of the PTB cell reports the four
    new metrics, each a share, and the driver's loop is covered.  (The
    four-chip cell's rehearsal stops before the optimizer is built: its
    ``tiny`` epoch of 16 records is 2 global batches of 8, fewer than
    the 3 ``check_losses`` — PERF.md section 7.)"""
    import json
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "ptb-medium-train-1chip", "--seed", "2147483659", "--seconds",
         "2", "--trace", "1", "--tiny", "--out", str(tmp_path)],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    for name in ("driver.unspanned_share", "driver.step_args_share",
                 "input.batch_pull_share", "input.block_stack_share"):
        assert metrics[name]["unit"] == "share"
        assert 0.0 <= metrics[name]["value"] < 1.0
    assert metrics["driver.unspanned_share"]["value"] < 0.2

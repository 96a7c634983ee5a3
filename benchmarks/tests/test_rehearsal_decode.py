"""``--tiny`` rehearsals on the CPU of the ``decode`` runner (end to
end, and with the step broken underneath: ``correct`` has to come out
false) and of ``resnet50-train-1chip``'s traffic file."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELL = "gpt2-xl-decode-saturated-1chip"   # held: benchmarks/held/


def _run(tmp_path, *extra, script=None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    head = [sys.executable] + (script or command[1:])
    return subprocess.run(
        head + ["--out", str(tmp_path)] + list(extra), cwd=ROOT, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=900)


def _checks(stderr):
    """``check <name>: <value> (limit <limit>) ok|NOT OK`` lines."""
    out = {}
    for line in stderr.splitlines():
        if line.startswith("check "):
            name, rest = line[len("check "):].split(": ", 1)
            out[name] = rest
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_decode_runner_tiny(tmp_path, trace):
    proc = _run(tmp_path, "--workload", CELL, "--seed", "2147483659",
                "--seconds", "3", "--trace", str(trace), "--tiny")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) - {"breakdown", "checks"} == KEYS
    # the numbers compared, each beside its limit, last on the line
    assert list(last)[-1] == "checks"
    assert set(last["checks"]["mean_gap"]) == {"value", "limit"}
    assert last["correct"] is False          # a rehearsal, never a result
    assert last["device"]["platform"] == "cpu"
    assert "correct=True" in lines[-2]       # the run's own checks passed
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "generator lateness, ms: mean" in proc.stdout
    # each number compared beside its limit: the last lines of stderr
    checks = _checks(proc.stderr)
    assert proc.stderr.strip().splitlines()[-1].startswith("check ")
    assert {"mean_gap", "widest_gap", "requests_failed", "answers_not_of_full_length",
            "generator_late_mean_ms", "compiles_in_window"} <= set(checks)
    assert all(v.endswith(" ok") for v in checks.values()), checks
    assert checks["answers_not_of_full_length"].startswith("0 ")
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert {"decode.slot_occupancy",
                "decode.itl_p50_ms"} <= set(last["metrics"])
        assert not {"decode_throughput",
                    "itl_p99_ms"} & set(last["metrics"])
    else:
        assert set(last["metrics"]) == {"decode_throughput", "itl_p99_ms",
                                        "setup_s"}
        assert all(m["value"] > 0 for m in last["metrics"].values())
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_slots",
                                   "token_altered", "shifted_position"])
def test_decode_step_broken_underneath_is_not_correct(tmp_path, fault):
    proc = _run(tmp_path, "--workload", CELL, "--seed", "2147483660",
                "--seconds", "2", "--trace", "0", "--tiny",
                script=[os.path.join(HERE, "decode_faults.py"), fault])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is False
    assert "correct=False" in lines[-2], lines[-2]
    assert _checks(proc.stderr)["widest_gap"].endswith("NOT OK")


def test_train_steady_1chip_tiny(tmp_path):
    proc = _run(tmp_path, "--workload", "resnet50-train-1chip", "--seed",
                "2147483661", "--seconds", "2", "--trace", "0", "--tiny")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is False and "correct=True" in lines[-2]
    assert set(last["metrics"]) == {"train_throughput", "setup_s"}
    assert last["attempted"] > 0 and last["failed"] == 0

"""The open-loop generator and the arithmetic on its stamps."""

import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import lib, open_loop  # noqa: E402

TRAFFIC = {"rate_per_s": 2.0, "warmup_s": 5.0, "block": 50,
           "prompt_len": {"distribution": "lognormal", "median": 256,
                          "sigma": 0.8, "min": 32, "max": 768},
           "output_len": {"distribution": "lognormal", "median": 64,
                          "sigma": 0.6, "min": 16, "max": 192}}


def test_n_and_due_times_follow_the_rate():
    s = open_loop.schedule(TRAFFIC, 7, 20.0, 1000)
    assert s["n"] == math.ceil(2.0 * 25.0) == 50
    assert s["due_s"][0] == 0.0 and np.all(np.diff(s["due_s"]) > 0)
    # the gaps are the quantiles of an exponential of mean 1 / rate
    gaps = np.sort(np.diff(s["due_s"]))
    want = np.sort(open_loop.gap_quantiles(2.0, 50))
    assert set(np.round(gaps, 9)) <= set(np.round(want, 9))
    assert abs(want.mean() - 0.5) < 0.02 and s["due_s"][-1] < 25.0


def test_same_multiset_for_two_seeds_in_another_order():
    a = open_loop.schedule(TRAFFIC, 1, 20.0, 1000)
    b = open_loop.schedule(TRAFFIC, 2 ** 31 + 11, 20.0, 1000)
    for key in ("prompt_len", "output_len"):
        assert sorted(a[key]) == sorted(b[key])
        assert list(a[key]) != list(b[key])
    # the same gaps in another order (the last of each order is the one
    # after the last request, and is not used)
    want = set(np.round(open_loop.gap_quantiles(2.0, a["n"]), 9))
    for s in (a, b):
        assert set(np.round(np.diff(s["due_s"]), 9)) <= want
    assert list(np.diff(a["due_s"])) != list(np.diff(b["due_s"]))
    assert [len(p) for p in a["prompts"]] == list(a["prompt_len"])
    assert a["prompts"][0].dtype == np.int32
    assert max(p.max() for p in a["prompts"]) < 1000
    again = open_loop.schedule(TRAFFIC, 1, 20.0, 1000)
    assert all(np.array_equal(p, q)
               for p, q in zip(a["prompts"], again["prompts"]))


def test_blocks_hold_the_same_multiset_each():
    tr = dict(TRAFFIC, block=8)
    a = open_loop.schedule(tr, 5, 20.0, 1000)
    b = open_loop.schedule(tr, 6, 20.0, 1000)
    assert a["n"] == 56 == open_loop.n_requests(tr, 20.0)   # 50 -> 7 x 8
    want = sorted(open_loop.length_quantiles(TRAFFIC["prompt_len"], 8))
    for s in (a, b):
        for k in range(0, 56, 8):
            assert sorted(s["prompt_len"][k:k + 8]) == want
            assert sorted(s["output_len"][k:k + 8]) == sorted(
                open_loop.length_quantiles(TRAFFIC["output_len"], 8))
    assert list(a["prompt_len"]) != list(b["prompt_len"])
    # every block takes the same time to arrive
    ends = a["due_s"][8::8]
    assert np.allclose(np.diff(ends), ends[0], atol=1e-9)


def test_lengths_are_clipped_quantiles():
    q = open_loop.length_quantiles(TRAFFIC["prompt_len"], 101)
    assert q.min() >= 32 and q.max() == 768 and q[50] == 256
    assert open_loop.length_quantiles(TRAFFIC["prompt_len"], 2001).min() == 32
    assert np.all(np.diff(q) >= 0)
    with pytest.raises(ValueError):
        open_loop.length_quantiles({"distribution": "zipf"}, 3)


@pytest.mark.parametrize("values,percent,want", [
    (list(range(1, 101)), 99.0, 99), (list(range(1, 101)), 50.0, 50),
    ([5.0, 1.0, 3.0], 99.0, 5.0), ([5.0, 1.0, 3.0], 34.0, 3.0),
    ([2.0], 99.0, 2.0), (list(range(1, 4001)), 99.0, 3960)])
def test_nearest_rank(values, percent, want):
    assert open_loop.nearest_rank(values, percent) == want


def test_nearest_rank_of_nothing_raises():
    with pytest.raises(ValueError):
        open_loop.nearest_rank([], 99.0)


def test_window_account_counts_tokens_and_whole_gaps():
    stamps = [[0.5, 1.0, 1.5, 2.5],      # first before, last after
              [1.2, 1.3],
              [],                        # never served
              [3.0, 3.5]]                # after the window
    acc = open_loop.window_account(stamps, 1.0, 2.0)
    assert acc["tokens"] == 4            # 1.0, 1.5, 1.2, 1.3
    assert sorted(round(g, 9) for g in acc["gaps_s"]) == [0.1, 0.5]


def test_decode_traffic_file_is_the_issues():
    tr = lib.load_json("traffic", "decode-open-saturated")
    assert tr["runner"] == "decode"
    assert tr["prompt_len"] == {"distribution": "lognormal", "median": 256,
                                "sigma": 0.8, "min": 32, "max": 768}
    assert tr["output_len"] == {"distribution": "lognormal", "median": 64,
                                "sigma": 0.6, "min": 16, "max": 192}
    assert (tr["queue_capacity"], tr["warmup_s"], tr["trace_seconds"]) \
        == (256, 5.0, 3.0)
    # NOT the issue's 1.25 x the knee: the file says why
    assert tr["rate_per_s"] == tr["rate_over_knee"] * tr["knee_per_s"]
    assert tr["rate_over_knee"] == 2.0 and tr["block"] == 8
    # the yardstick starts no profiler to steer the runtime (REVIEW 34)
    assert not [k for k in tr if k.startswith("profiler")]
    s = open_loop.schedule(tr, 3, 20.0, 50257)
    assert s["n"] == 48
    assert max(s["prompt_len"]) + max(s["output_len"]) <= 960 < 1024


def test_train_steady_1chip_is_train_steady_under_its_own_name():
    a = lib.load_json("traffic", "train-steady")
    b = lib.load_json("traffic", "train-steady-1chip")
    for key in ("runner", "warmup_blocks", "trace_seconds",
                "check_losses", "tiny"):
        assert a[key] == b[key]
    assert b["name"] == "train-steady-1chip"

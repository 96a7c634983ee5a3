"""Spans and counters inside ``DecodeService`` (ISSUE 37).

What is held here, at the tiny size on the CPU:

- **inert**: tokens bit for bit and the same executable launches with
  and without a tracer; without one no span is made;
- **tiling**: ``idle`` / ``schedule`` / ``admit`` / ``step`` cover the
  scheduler thread's time between the first admission and the last
  finish, never overlap, and every child lies inside its parent;
- **a request's own spans**: ``queue_wait`` ends and ``sequence`` starts
  on one stamp just before its ``admit``; all three carry ``req``;
- **counters**: each against a count made by hand from the prompt
  lengths, buckets and answer lengths of ``REQS``;
- **running state**: ``stats()`` reads the spans from histograms fed as
  they close, so it goes on past the tracer's ``capacity``;
- **the mirror**: a CPU profiler capture of the service, reduced by
  ``benchmarks/host_spans.py`` as it stands;
- ``tools/trace_report`` reads the service's Chrome trace.
"""

import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from bigdl_tpu.models.transformer import transformer_lm
from bigdl_tpu.serving import DeadlineExceeded, DecodeService
from bigdl_tpu.serving.decode import SPAN_CATS
from bigdl_tpu.telemetry import RequestContext, Tracer
from bigdl_tpu.telemetry import admin as admin_mod
from bigdl_tpu.telemetry.tracer import (DECODE_PHASE_CATS,
                                        DECODE_TOP_LEVEL_CATS)
from bigdl_tpu.utils import config as config_mod
from tools import trace_report

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB, SLOTS, MAX_LEN = 64, 2, 64
BUCKETS = [8, 16, 32, 63]           # pow2@8 under max_prompt_len 63
# (prompt length, answer length).  Two slots, all four queued before the
# scheduler starts, so the schedule is one: A and B admitted, two steps,
# B done; C admitted and done on its first token; a step; D admitted;
# A done after step 5, D after step 10.
REQS = ((5, 6), (9, 3), (17, 1), (3, 8))
STEPS = 10
PADDED = (8, 16, 32, 8)
# a sequence of prompt n and answer g rides g - 1 steps, over n, n + 1,
# ... positions: 35 + 19 + 0 + 42
KV_USED = sum(sum(range(n, n + g - 1)) for n, g in REQS)
LP_BYTES = VOCAB * 4                # one position's f32 log-probabilities
FIRST_FETCH = sum(PADDED) * LP_BYTES

PARENT_OF = {"prefill_launch": "admit", "splice_launch": "admit",
             "first_fetch": "admit", "step_h2d": "step",
             "dispatch": "step", "device_wait": "step",
             "step_fetch": "step"}   # ``emit`` lies in either


@pytest.fixture(scope="module")
def lm():
    return transformer_lm(vocab_size=VOCAB, embed_dim=32, num_heads=4,
                          num_layers=2, max_len=MAX_LEN).initialize(0)


class CountingTracer(Tracer):
    def __init__(self):
        super().__init__()
        self.span_calls = 0

    def span(self, name, cat=None, **args):
        self.span_calls += 1
        return super().span(name, cat=cat, **args)


class Launches:
    """Counts the calls of one of the service's executables."""

    def __init__(self, exe):
        self.exe, self.n = exe, 0

    def __call__(self, *args):
        self.n += 1
        return self.exe(*args)


def prompts():
    rng = np.random.default_rng(7)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n, _g in REQS]


def serve(lm, tracer, on_token=None, ctx_for=()):
    """``REQS`` through one service, queued before its thread starts.
    Returns the service (stopped), the results and the launch counts."""
    svc = DecodeService(lm, slots=SLOTS, max_seq_len=MAX_LEN,
                        tracer=tracer, start=False)
    counts = {"step": Launches(svc._step_exec)}
    svc._step_exec = counts["step"]
    for kind, table in (("prefill", svc._prefill_exec),
                        ("splice", svc._splice_exec)):
        for tb in table:
            table[tb] = counts.setdefault(f"{kind}-{tb}",
                                          Launches(table[tb]))
    futs = [svc.submit(p, max_new_tokens=g, on_token=on_token,
                       ctx=RequestContext() if i in ctx_for else None)
            for i, (p, (_n, g)) in enumerate(zip(prompts(), REQS))]
    svc.start()
    results = [f.result(timeout=120) for f in futs]
    svc.stop(timeout=60)
    assert not svc.alive
    return svc, results, {k: c.n for k, c in counts.items()}


def spans_of(tracer):
    """``(thread spans, track spans)`` as dicts."""
    thread, tracks = [], []
    for ph, name, cat, t0, dur, tid, args, _flow in tracer.events():
        assert ph == "X"
        row = dict(name=name, cat=cat, t0=t0, t1=t0 + dur, tid=tid,
                   args=args or {})
        (tracks if isinstance(tid, str) else thread).append(row)
    return thread, tracks


@pytest.fixture(scope="module")
def traced(lm):
    """One traced run whose steps take tens of milliseconds (the
    callback sleeps), as a step on the chip does: the loop's own
    bookkeeping between two top-level spans is tens of microseconds."""
    tracer = CountingTracer()
    svc, results, launches = serve(
        lm, tracer, on_token=lambda _i, _t: time.sleep(0.02),
        ctx_for=(1,))
    return dict(svc=svc, tracer=tracer, results=results,
                launches=launches, stats=svc.stats()["decode"])


@pytest.fixture(scope="module")
def untraced(lm):
    svc, results, launches = serve(lm, None)
    return dict(svc=svc, results=results, launches=launches,
                stats=svc.stats()["decode"])


# ------------------------------------------------------------ inertness
class TestInert:
    def test_tokens_bit_for_bit(self, traced, untraced):
        for a, b in zip(traced["results"], untraced["results"]):
            assert a.tokens.tolist() == b.tokens.tolist()
            assert (a.admit_step, a.finish_step, a.slot) == \
                (b.admit_step, b.finish_step, b.slot)
        assert [len(r.tokens) for r in traced["results"]] == \
            [g for _n, g in REQS]

    def test_same_executable_launches(self, traced, untraced):
        assert traced["launches"] == untraced["launches"]
        assert untraced["launches"]["step"] == STEPS
        assert sum(v for k, v in untraced["launches"].items()
                   if k.startswith("prefill")) == len(REQS)

    def test_no_tracer_no_span(self, lm, monkeypatch):
        made = []
        real = Tracer.span
        monkeypatch.setattr(
            Tracer, "span",
            lambda self, *a, **k: made.append(a) or real(self, *a, **k))
        svc, _results, _n = serve(lm, None)
        assert svc.tracer is None and made == []
        assert "spans" not in svc.stats()["decode"]
        assert not [n for n in svc.metrics.registry.snapshot()["histograms"]
                    if n.startswith("decode/span_ms/")]
        # a tracer that is off is not held either
        assert DecodeService(lm, slots=1, tracer=Tracer(enabled=False),
                             start=False).tracer is None

    def test_span_calls_by_hand(self, traced):
        # a pass: one schedule; an admission 1 + 4; a step 1 + 5; the
        # passes are the 10 steps' and the one that finds the stop
        idles = sum(1 for s in spans_of(traced["tracer"])[0]
                    if s["name"] == "idle")
        assert traced["tracer"].span_calls == \
            idles + (STEPS + 1) + 5 * len(REQS) + 6 * STEPS

    def test_env_switch_makes_the_service_its_own_tracer(self, lm,
                                                         monkeypatch):
        monkeypatch.setenv("BIGDL_TPU_TELEMETRY", "1")
        config_mod.reset_config()
        try:
            svc = DecodeService(lm, slots=1, start=False)
            assert isinstance(svc.tracer, Tracer) and svc.tracer.enabled
        finally:
            monkeypatch.delenv("BIGDL_TPU_TELEMETRY")
            config_mod.reset_config()
        assert DecodeService(lm, slots=1, start=False).tracer is None

    def test_registers_with_the_admin_plane_and_leaves_it(self, lm):
        srv = admin_mod.AdminServer(port=0)
        admin_mod.install(srv)
        try:
            tracer = Tracer()
            svc = DecodeService(lm, slots=1, tracer=tracer, name="dec")
            assert srv._tracers == {"dec": tracer}
            assert srv._registries["dec"] is svc.metrics.registry
            svc.generate(prompts()[0], max_new_tokens=2)
            names = {e["name"] for e in srv.trace_json()["traceEvents"]}
            assert {"admit", "step", "dispatch", "sequence"} <= names
            svc.stop(timeout=60)
            assert srv._tracers == {} and srv._registries == {}
        finally:
            admin_mod.install(None)


# --------------------------------------------------------------- tiling
class TestTiling:
    def test_top_level_spans_cover_the_scheduler_thread(self, traced):
        thread, _tracks = spans_of(traced["tracer"])
        assert len({s["tid"] for s in thread}) == 1
        tops = sorted((s for s in thread
                       if s["cat"] in DECODE_TOP_LEVEL_CATS),
                      key=lambda s: s["t0"])
        for a, b in zip(tops, tops[1:]):
            assert a["t1"] <= b["t0"]          # they tile, never overlap
        first = next(i for i, s in enumerate(tops) if s["name"] == "admit")
        # the last finish lies in the last step
        last = max(i for i, s in enumerate(tops) if s["name"] == "step")
        window = tops[first:last + 1]
        covered = sum(s["t1"] - s["t0"] for s in window)
        wall = window[-1]["t1"] - window[0]["t0"]
        assert wall / STEPS > 2e7               # steps of 20 ms and more
        assert covered / wall >= 0.99, (covered, wall)
        assert traced["stats"]["loop_unspanned_share"] < 0.05

    def test_names_and_categories(self, traced):
        thread, tracks = spans_of(traced["tracer"])
        assert {s["cat"] for s in thread + tracks} <= set(DECODE_PHASE_CATS)
        want = {"idle": "decode_idle", "schedule": "decode_schedule",
                "admit": "decode_admit", "step": "decode_step",
                "prefill_launch": "decode_launch",
                "splice_launch": "decode_launch",
                "dispatch": "decode_launch", "first_fetch": "decode_fetch",
                "step_fetch": "decode_fetch", "step_h2d": "decode_h2d",
                "device_wait": "decode_device_wait", "emit": "decode_emit"}
        assert {(s["name"], s["cat"]) for s in thread} \
            >= set(want.items()) - {("idle", "decode_idle")}
        assert {(s["name"], s["cat"]) for s in thread} <= set(want.items())
        assert SPAN_CATS == want
        for s in thread:
            assert DECODE_PHASE_CATS[s["cat"]] is not None
        for s in tracks:
            assert DECODE_PHASE_CATS[s["cat"]] is None

    def test_every_child_inside_its_parent(self, traced):
        thread, _tracks = spans_of(traced["tracer"])

        def inside(c, p):
            return p["t0"] <= c["t0"] and c["t1"] <= p["t1"]

        for s in thread:
            if s["cat"] in DECODE_TOP_LEVEL_CATS:
                continue
            parents = ("admit", "step") if s["name"] == "emit" \
                else (PARENT_OF[s["name"]],)
            assert sum(1 for p in thread if p["name"] in parents
                       and inside(s, p)) == 1, s

    def test_arguments(self, traced):
        thread, _tracks = spans_of(traced["tracer"])
        by = {}
        for s in thread:
            by.setdefault(s["name"], []).append(s)
        assert [s["args"]["step"] for s in by["step"]] == list(range(STEPS))
        assert all(1 <= s["args"]["active"] <= SLOTS for s in by["step"])
        assert sorted((s["args"]["prompt_len"], s["args"]["bucket"])
                      for s in by["admit"]) == \
            sorted((n, tb) for (n, _g), tb in zip(REQS, PADDED))
        # the wait is the request's ``queue_wait`` span, not an argument
        assert all(set(s["args"]) == {"req", "slot", "prompt_len", "bucket"}
                   for s in by["admit"])
        # (the pass that finds the stop picks nothing and says nothing)
        assert sum(s["args"].get("picked", 0)
                   for s in by["schedule"]) == len(REQS)
        assert by["schedule"][0]["args"]["queued"] == len(REQS)
        assert by["schedule"][0]["args"]["free"] == SLOTS
        assert sum(s["args"]["bytes"] for s in by["first_fetch"]) \
            == FIRST_FETCH
        assert {s["args"]["bytes"] for s in by["step_fetch"]} \
            == {SLOTS * LP_BYTES}
        assert {s["args"]["bytes"] for s in by["step_h2d"]} \
            == {2 * SLOTS * 4}
        n_leaves = len(jax.tree_util.tree_leaves(traced["svc"]._params))
        assert {s["args"]["args"] for s in by["dispatch"]} \
            == {n_leaves + 4}
        step_emits = [s for s in by["emit"] if "tokens" in s["args"]]
        assert len(step_emits) == STEPS
        # every token but each answer's first comes from a step
        assert sum(s["args"]["tokens"] for s in step_emits) \
            == sum(g - 1 for _n, g in REQS)


# ------------------------------------------------- a request's own spans
class TestRequestSpans:
    def test_queue_wait_and_sequence_abut_the_admit(self, traced):
        thread, tracks = spans_of(traced["tracer"])
        admits = {s["args"]["req"]: s for s in thread
                  if s["name"] == "admit"}
        waits = {s["args"]["req"]: s for s in tracks
                 if s["name"] == "queue_wait"}
        seqs = {s["args"]["req"]: s for s in tracks
                if s["name"] == "sequence"}
        assert len(admits) == len(waits) == len(seqs) == len(REQS)
        assert set(admits) == set(waits) == set(seqs)
        for req, adm in admits.items():
            w, q = waits[req], seqs[req]
            assert (w["cat"], w["tid"]) == ("decode_queue", "queue")
            assert (q["cat"], q["tid"]) == \
                ("decode_sequence", f"slot-{adm['args']['slot']}")
            assert w["t0"] < w["t1"] == q["t0"] <= adm["t0"]
            assert adm["t0"] - w["t1"] < 1e6     # a stamp and a record
            assert q["t1"] >= q["t0"]

    def test_req_is_the_trace_id_or_the_submission_count(self, traced):
        _thread, tracks = spans_of(traced["tracer"])
        reqs = {s["args"]["req"] for s in tracks}
        counts = {r for r in reqs if isinstance(r, int)}
        ids = reqs - counts
        assert counts == {1, 3, 4}              # REQS[1] came with a ctx
        assert len(ids) == 1 and isinstance(next(iter(ids)), str)

    def test_sequence_carries_the_steps_it_rode(self, traced):
        thread, tracks = spans_of(traced["tracer"])
        by_slot_step = {}
        for s in tracks:
            if s["name"] == "sequence":
                by_slot_step[(int(s["tid"].split("-")[1]),
                              s["args"]["admit_step"])] = s
        steps = {s["args"]["step"]: s for s in thread
                 if s["name"] == "step"}
        for res, (_n, g) in zip(traced["results"], REQS):
            q = by_slot_step[(res.slot, res.admit_step)]
            assert q["args"]["finish_step"] == res.finish_step
            assert q["args"]["tokens"] == g
            assert q["args"]["reason"] == "length"
            # it rode steps admit_step .. finish_step - 1, and ended in
            # the last of them (or, with one token, in its admit)
            assert res.finish_step - res.admit_step == g - 1
            if g > 1:
                last = steps[res.finish_step - 1]
                assert last["t0"] <= q["t1"] <= last["t1"]
        # sequences of one slot never overlap
        for slot in range(SLOTS):
            rows = sorted((s for s in tracks if s["tid"] == f"slot-{slot}"),
                          key=lambda s: s["t0"])
            for a, b in zip(rows, rows[1:]):
                assert a["t1"] <= b["t0"]


# ------------------------------------------------------------- counters
@pytest.mark.parametrize("which", ["traced", "untraced"])
@pytest.mark.parametrize("key,want", [
    ("steps", STEPS), ("admissions", len(REQS)),
    ("tokens_generated", sum(g for _n, g in REQS)),
    ("kv_positions_used", KV_USED),
    ("kv_positions_reserved", STEPS * SLOTS * MAX_LEN),
    ("prefill_tokens", sum(n for n, _g in REQS)),
    ("prefill_tokens_padded", sum(PADDED)),
    ("first_fetch_bytes", FIRST_FETCH),
    ("slots_reclaimed", len(REQS)),
    ("expired_before_admit", 0), ("expired_mid_decode", 0)])
def test_counter_against_a_hand_count(which, key, want, request):
    assert (KV_USED, sum(PADDED)) == (96, 64)
    assert request.getfixturevalue(which)["stats"][key] == want


@pytest.mark.parametrize("which", ["traced", "untraced"])
def test_histograms_count_admissions_and_steps(which, request):
    run = request.getfixturevalue(which)
    st = run["stats"]
    assert st["queue_wait_ms"]["count"] == len(REQS)
    assert st["step_ms"]["count"] == STEPS
    assert 0 < st["step_ms"]["p50"] <= st["step_ms"]["p95"] \
        <= st["step_ms"]["p99"]
    assert st["queue_wait_ms"]["min"] >= 0
    assert st["prefill_buckets"] == BUCKETS
    snap = run["svc"].metrics.registry.snapshot()
    assert snap["counters"]["decode/kv_positions_used"] == KV_USED
    assert snap["histograms"]["decode/step_ms"]["count"] == STEPS


def test_stats_hold_the_spans_running_sums(traced):
    st = traced["stats"]
    rows = st["spans"]
    assert set(rows) == set(SPAN_CATS) - (
        set() if "idle" in rows else {"idle"})
    assert {n: r["cat"] for n, r in rows.items()} \
        == {n: SPAN_CATS[n] for n in rows}
    want = {"step": STEPS, "dispatch": STEPS, "device_wait": STEPS,
            "step_fetch": STEPS, "step_h2d": STEPS,
            "admit": len(REQS), "prefill_launch": len(REQS),
            "splice_launch": len(REQS), "first_fetch": len(REQS),
            "emit": STEPS + len(REQS), "schedule": STEPS + 1}
    assert {n: rows[n]["spans"] for n in want} == want
    # the sums are the tracer's own durations, span for span
    by_name = {}
    for s in spans_of(traced["tracer"])[0]:
        by_name.setdefault(s["name"], []).append(s["t1"] - s["t0"])
    for name, row in rows.items():
        assert row["seconds"] == pytest.approx(sum(by_name[name]) / 1e9)
        assert min(by_name[name]) / 1e6 <= row["median_ms"] \
            <= max(by_name[name]) / 1e6
    # a step less its wait for the device: what the host adds
    assert 0 < st["host_step_ms"] <= rows["step"]["median_ms"]
    hists = traced["svc"].metrics.registry.snapshot()["histograms"]
    assert hists["decode/host_step_ms"]["count"] == STEPS
    assert hists["decode/span_ms/dispatch"]["count"] == STEPS
    assert st["trace_dropped_events"] == 0


def test_stats_go_on_past_the_tracers_capacity(lm):
    # the buffer holds its first 10 events and drops the rest; the
    # running sums and the tiling's own figure do not notice
    tracer = Tracer(capacity=10)
    svc, _results, _n = serve(lm, tracer)
    st = svc.stats()["decode"]
    assert len(tracer.events()) == 10 and st["trace_dropped_events"] > 0
    assert st["spans"]["step"]["spans"] == STEPS
    assert st["spans"]["admit"]["spans"] == len(REQS)
    assert st["spans"]["emit"]["spans"] == STEPS + len(REQS)
    assert st["host_step_ms"] > 0
    assert 0 <= st["loop_unspanned_share"] < 0.5


# -------------------------------------------------------------- expiries
class TestExpiries:
    def test_expired_before_admit(self, lm):
        tracer = Tracer()
        with DecodeService(lm, slots=1, tracer=tracer) as svc:
            fut = svc.submit(prompts()[0], max_new_tokens=4,
                             deadline=time.monotonic() - 1.0)
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=60)
            st = svc.stats()["decode"]
        assert (st["expired_before_admit"], st["expired_mid_decode"]) \
            == (1, 0)
        assert st["admissions"] == 0 and st["queue_wait_ms"]["count"] == 0
        thread, tracks = spans_of(tracer)
        assert [s["name"] for s in tracks] == ["queue_wait"]
        # the pass is still tiled: its admit span holds the refusal
        assert [s["name"] for s in thread if s["name"] == "admit"] \
            == ["admit"]
        assert not [s for s in thread if s["name"] == "prefill_launch"]

    def test_expired_mid_decode(self, lm):
        tracer = Tracer()
        late = threading.Event()

        def slow_first(index, _token):
            if index == 0:
                time.sleep(0.6)          # past the deadline, in its admit
                late.set()

        with DecodeService(lm, slots=1, tracer=tracer) as svc:
            fut = svc.submit(prompts()[0], max_new_tokens=8,
                             deadline=time.monotonic() + 0.5,
                             on_token=slow_first)
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=60)
            st = svc.stats()["decode"]
        assert late.is_set()
        assert (st["expired_before_admit"], st["expired_mid_decode"]) \
            == (0, 1)
        assert st["admissions"] == 1 and st["steps"] == 1
        _thread, tracks = spans_of(tracer)
        seq = [s for s in tracks if s["name"] == "sequence"]
        assert len(seq) == 1
        assert seq[0]["args"]["reason"] == "DeadlineExceeded"
        assert seq[0]["args"]["tokens"] == 1


# ------------------------------------------------- the mirror, read back
def test_profiler_capture_reduced_by_host_spans_as_it_stands(lm, tmp_path):
    sys.path.insert(0, ROOT)
    try:
        from benchmarks import host_spans, trace_reduce
    finally:
        sys.path.remove(ROOT)
    tracer = Tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1        # what the decode runner sets
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _svc, results, _n = serve(lm, tracer)
    finally:
        jax.profiler.stop_trace()
    assert [len(r.tokens) for r in results] == [g for _n, g in REQS]
    red = host_spans.reduce_file(trace_reduce.find_xplane(str(tmp_path)))
    # the scheduler's thread, found by its ``dispatch`` span
    assert red["driver_line"] is not None
    assert len(red["threads"]) == 1
    cats = red["categories"]
    on_thread = {c for c, top in DECODE_PHASE_CATS.items()
                 if top is not None}
    assert on_thread - {"decode_idle"} <= set(cats) <= on_thread
    assert cats["decode_step"]["spans"] == STEPS
    assert cats["decode_admit"]["spans"] == len(REQS)
    assert cats["decode_launch"]["spans"] == STEPS + 2 * len(REQS)
    for row in cats.values():
        assert 0 <= row["self_seconds"] <= row["seconds"] + 1e-9
    # children never exceed their parents: what admit and step keep to
    # themselves is what their children leave
    children = sum(cats[c]["seconds"] for c in cats
                   if not DECODE_PHASE_CATS[c])
    parents = cats["decode_admit"]["seconds"] + cats["decode_step"]["seconds"]
    assert children <= parents
    assert cats["decode_admit"]["self_seconds"] \
        + cats["decode_step"]["self_seconds"] \
        == pytest.approx(parents - children, abs=1e-6)
    # off the chip there is no device plane: no gap to name
    assert red["gaps"] == []


def test_trace_report_reads_a_decode_trace(traced, tmp_path, capsys):
    path = traced["tracer"].dump(str(tmp_path / "decode.json"))
    report = trace_report.summarize(trace_report.load_trace(path))
    share = report["phase_share"]
    on_thread = {c for c, top in DECODE_PHASE_CATS.items()
                 if top is not None}
    assert set(share) - {"other"} <= on_thread
    assert sum(share.values()) == pytest.approx(1.0, abs=2e-3)
    # the decode loop's four top-level categories are its coverage,
    # apart from the training driver's
    assert report["decode_coverage"] >= 0.95
    assert report["driver_coverage"] == 0.0
    assert set(report["off_driver_share"]) == {"decode_queue",
                                               "decode_sequence"}
    assert report["stall"]["device_wait_fraction"] == 0.0
    assert report["stall"]["decode_device_wait_fraction"] == pytest.approx(
        sum(s["t1"] - s["t0"] for s in spans_of(traced["tracer"])[0]
            if s["name"] == "device_wait") / 1e9 / report["wall_s"],
        abs=1e-3)
    assert trace_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "decode_step*" in out and "decode_launch " in out
    assert "decode coverage" in out and "decode_sequence" in out
    assert "decode_device_wait 0." in out


def test_trace_report_keeps_the_two_loops_apart():
    # one tracer shared by a training driver and a decode service, both
    # busy for the whole second: each loop's coverage is its own
    tracer = Tracer()
    ms = 1_000_000
    for t0, t1, cat in ((0, 200, "stage_next"), (200, 300, "dispatch"),
                        (300, 900, "device_wait"), (900, 1000, "replay")):
        tracer.record(cat, t0 * ms, t1 * ms, cat=cat, track="driver")
    for i in range(10):
        tracer.record("step", i * 100 * ms, (i * 100 + 95) * ms,
                      cat="decode_step", track="sched")
        tracer.record("device_wait", (i * 100 + 5) * ms,
                      (i * 100 + 85) * ms, cat="decode_device_wait",
                      track="sched")
    report = trace_report.summarize(tracer.to_chrome_trace())
    assert report["wall_s"] == pytest.approx(1.0)
    assert report["driver_coverage"] == 1.0
    assert report["decode_coverage"] == 0.95
    assert report["stall"]["device_wait_fraction"] == 0.6
    assert report["stall"]["decode_device_wait_fraction"] == 0.8

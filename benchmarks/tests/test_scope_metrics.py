"""The table of named device scopes (``hlo_scopes.py``) on the text of
a module compiled here, and the five per-layer readers of PR 32 on a
recorded observation: what each reads, and ``None`` (so that the line
leaves the metric out) where the program has no such scope or counter,
as the parent commit has not."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import hlo_scopes, lib  # noqa: E402

NEW = ["moe.device_share", "ssm.device_share", "moe.row_fill",
       "moe.rows_overflow", "moe.experts_roofline"]

# one traced step and a bit (1.25 whole executions), as the runner
# records it: 10 ms busy; 4 ms in the experts' products, 1 ms in the
# router, 2 ms in the scan, 3 ms under no scope
OBSERVED = {
    "chips": 1,
    "peaks": {"bf16_flops_per_s": 100e12},
    "trace_steps": 1.25,
    "trace_device0": {
        "busy_s": 0.010,
        "op_self_s": {"ragged-dot-none.1": 0.003, "fusion.7": 0.001,
                      "fusion.8": 0.001, "while.2": 0.002,
                      "fusion.9": 0.003}},
    "scopes": {"ragged-dot-none.1": "bigdl.moe.experts",
               "fusion.7": "bigdl.moe.experts",
               "fusion.8": "bigdl.moe.route",
               "while.2": "bigdl.mamba.scan",
               "not.in.the.trace": "bigdl.head"},
    # 50 steps of 2 layers with 1,000 rows each; 800 held a layer a step
    "moe_counters": {"layers": 2, "steps": 50, "rows_held": 80000,
                     "rows_overflow": 0, "rows": 100000,
                     "flops_per_row": 1e8},
}
# what the parent commit gives this PR's readers: a traced step, no
# scope table, no counters
PARENT = {"chips": 1, "peaks": {"bf16_flops_per_s": 100e12},
          "trace_steps": 1.25,
          "trace_device0": {"busy_s": 0.010,
                            "op_self_s": {"fusion.9": 0.010}}}


def read(name, obs):
    return lib.load_module("layer_metrics", name).read(obs)


def test_reads_what_it_says():
    assert read("moe.device_share", OBSERVED) == pytest.approx(0.5)
    assert read("ssm.device_share", OBSERVED) == pytest.approx(0.2)
    assert read("moe.row_fill", OBSERVED) == pytest.approx(0.8)
    assert read("moe.rows_overflow", OBSERVED) == 0.0
    # 1,600 rows a step x 1e8 = 1.6e11 operations in 4 ms / 1.25 steps
    # = 3.2 ms: 5e13 a second, half the peak
    assert read("moe.experts_roofline", OBSERVED) == pytest.approx(50.0)


def test_roofline_counts_the_counter_not_the_buffer():
    fuller = dict(OBSERVED, moe_counters=dict(
        OBSERVED["moe_counters"], rows=200000))
    assert read("moe.experts_roofline", fuller) == pytest.approx(50.0)
    assert read("moe.row_fill", fuller) == pytest.approx(0.4)


def test_an_overflow_is_reported_as_counted():
    over = dict(OBSERVED, moe_counters=dict(
        OBSERVED["moe_counters"], rows_overflow=7))
    assert read("moe.rows_overflow", over) == 7.0


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [PARENT, {}, {"trace_device0": None,
                                              "moe_counters": None}],
                         ids=["parent", "empty", "none"])
def test_none_where_the_program_has_no_such_thing(name, obs):
    assert read(name, obs) is None


def test_roofline_is_none_off_the_chip():
    assert read("moe.experts_roofline", dict(OBSERVED, peaks=None)) is None


def test_declared_in_benchmark_json():
    bench = lib.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cell = "granite-4.0-h-small-train-8k-1chip"
    for name in NEW:
        assert by_name[name]["workloads"] == [cell]
        assert by_name[name]["moves"] == "train_throughput"
    assert by_name["moe.experts_roofline"]["unit"] == "%"
    assert cell not in by_name["kernel.pallas_share"]["workloads"]


# ----------------------------------------------------- the scope table
def test_scope_of_takes_the_innermost():
    assert hlo_scopes.scope_of(
        "jit(body)/transpose(jvp(bigdl.moe.experts))/mul") == \
        "bigdl.moe.experts"
    assert hlo_scopes.scope_of(
        "jit(f)/bigdl.attention/checkpoint/bigdl.mamba.scan/while") == \
        "bigdl.mamba.scan"
    assert hlo_scopes.scope_of("jit(f)/jit(main)/dot_general") is None


def test_table_from_a_compiled_module():
    """The product's ``device_scope`` ends up in the compiled module's
    ``op_name`` metadata, forward and backward, through a checkpoint."""
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.telemetry import device_scope

    @jax.checkpoint
    def layer(w, x):
        with device_scope("moe.experts"):
            h = jnp.tanh(x @ w)
        with device_scope("head"):
            return jnp.sum(jnp.sin(h) ** 2)

    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    text = jax.jit(jax.grad(layer)).lower(w, x).compile().as_text()
    table = hlo_scopes.instruction_scopes(text)
    assert {"bigdl.moe.experts", "bigdl.head"} <= set(table.values())
    # a program without scopes gives an empty table, and no error
    plain = jax.jit(lambda a: a @ a).lower(w).compile().as_text()
    assert hlo_scopes.instruction_scopes(plain) == {}
    assert hlo_scopes.seconds_under({"trace_device0": {"op_self_s": {
        "fusion": 1.0}}, "scopes": {}}, "bigdl.moe.") is None


def test_compiler_made_instructions_are_placed_by_name():
    text = "\n".join([
        'ENTRY %main {',
        '  %ragged-dot-none.79 = bf16[8,4]{1,0} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-none"}',
        '  %fusion.3 = bf16[8,2]{1,0} fusion(%ragged-dot-none.79), '
        'kind=kLoop, metadata={op_name="jit(body)/jvp(bigdl.moe.experts)'
        '/mul" stack_frame_id=1}',
        '  ROOT %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, '
        'metadata={op_name="jit(body)/mul"}',
        '}'])
    assert hlo_scopes.instruction_scopes(text) == {
        "fusion.3": "bigdl.moe.experts"}
    assert hlo_scopes.instruction_scopes(
        text, {"ragged-dot": "bigdl.moe.experts"}) == {
        "fusion.3": "bigdl.moe.experts",
        "ragged-dot-none.79": "bigdl.moe.experts"}
    # a kernel the table places nowhere is named, so that the runner
    # can refuse the run: its time would be read as nobody's (before the
    # builder placed ``ragged-dot``, moe.experts_roofline read 788 %)
    assert hlo_scopes.unscoped_kernels(
        text, hlo_scopes.instruction_scopes(text)) == ["ragged-dot-none.79"]
    assert hlo_scopes.unscoped_kernels(text, hlo_scopes.instruction_scopes(
        text, {"ragged-dot": "bigdl.moe.experts"})) == []
    renamed = text.replace("%ragged-dot-none.79 =", "%grouped-matmul.79 =")
    assert hlo_scopes.unscoped_kernels(renamed, hlo_scopes.instruction_scopes(
        renamed, {"ragged-dot": "bigdl.moe.experts"})) == [
        "grouped-matmul.79"]


def test_seconds_by_scope_leaves_the_unscoped_out():
    by = hlo_scopes.seconds_by_scope(
        OBSERVED["trace_device0"]["op_self_s"], OBSERVED["scopes"])
    assert by == pytest.approx({"bigdl.moe.experts": 0.004,
                                "bigdl.moe.route": 0.001,
                                "bigdl.mamba.scan": 0.002})
    assert json.dumps(by)               # plain numbers


# ------------------------------- train_lm's comparison of the parameters
def _lm():
    return lib.load_module("runners", "train_lm")


def _trees():
    """Three leaves as the runner samples them: two layers' ``A_log``
    and a large weight whose change is a thousand times larger."""
    import numpy as np
    rng = np.random.default_rng(0)
    initial = {"layers.0.mixer.A_log": rng.normal(size=16),
               "layers.1.mixer.A_log": rng.normal(size=16),
               "layers.0.experts.w_in": rng.normal(size=4096)}
    change = {"layers.0.mixer.A_log": 1e-3 * rng.normal(size=16),
              "layers.1.mixer.A_log": 1e-3 * rng.normal(size=16),
              "layers.0.experts.w_in": rng.normal(size=4096)}
    reference = {k: initial[k] + change[k] for k in initial}
    return initial, change, reference


def test_leaf_kind_drops_the_layers_number():
    lm = _lm()
    assert lm.leaf_kind("layers.3.mixer.A_log") == "mixer.A_log"
    assert lm.leaf_kind("layers.12.norm1") == "norm1"
    assert lm.leaf_kind("embed") == "embed"


def test_one_small_leaf_left_unchanged_is_caught():
    """The fault one global norm hides: a small leaf that learned
    nothing moves the norm by a millionth and reads 1 as a leaf."""
    lm = _lm()
    initial, change, reference = _trees()
    got = {k: reference[k] + 0.03 * change[k] for k in reference}
    got["layers.1.mixer.A_log"] = initial["layers.1.mixer.A_log"]
    assert lm.global_error(initial, reference, got) < 0.031
    errors = lm.param_change_errors(initial, reference, got)
    assert errors["layers.1.mixer.A_log"] == pytest.approx(1.0)
    assert errors["layers.0.mixer.A_log"] == pytest.approx(0.03)
    assert lm.worst_by_kind(errors)["mixer.A_log"] == (
        errors["layers.1.mixer.A_log"], "layers.1.mixer.A_log")
    ok, lines = lm.params_correct(errors, {"*": 0.2})
    assert not ok and lines[0].startswith("mixer.A_log: 1.0000e+00 at "
                                          "layers.1.mixer.A_log")
    got["layers.1.mixer.A_log"] = reference["layers.1.mixer.A_log"]
    assert lm.params_correct(lm.param_change_errors(
        initial, reference, got), {"*": 0.2})[0]


def test_a_kind_has_its_own_limit():
    lm = _lm()
    initial, change, reference = _trees()
    got = {k: reference[k] + (0.1 if "w_in" in k else 0.01) * change[k]
           for k in reference}
    errors = lm.param_change_errors(initial, reference, got)
    assert lm.params_correct(errors, {"*": 0.2})[0]
    assert not lm.params_correct(errors, {"*": 0.2,
                                          "experts.w_in": 0.05})[0]
    assert not lm.params_correct(errors, {"*": 0.2,
                                          "mixer.A_log": 0.005})[0]


@pytest.mark.parametrize("moved, want", [(False, 0.0), (True, "inf")],
                         ids=["unchanged", "moved"])
def test_a_leaf_the_reference_left_unchanged(moved, want):
    """No gradient in the reference: the product must leave it too."""
    lm = _lm()
    initial, _, reference = _trees()
    reference["layers.0.mixer.A_log"] = initial["layers.0.mixer.A_log"]
    got = dict(reference)
    if moved:
        got["layers.0.mixer.A_log"] = reference["layers.0.mixer.A_log"] + 1
    errors = lm.param_change_errors(initial, reference, got)
    assert errors["layers.0.mixer.A_log"] == float(want)
    assert lm.params_correct(errors, {"*": 0.2})[0] is not moved
    import numpy as np
    nan = dict(got, **{"layers.0.experts.w_in":
                       got["layers.0.experts.w_in"] * np.nan})
    assert not lm.params_correct(lm.param_change_errors(
        initial, reference, nan), {"*": 0.2})[0]

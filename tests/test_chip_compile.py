"""Every ``pallas_call`` of the main path, compiled for a DESCRIBED
TPU v5e at the widths ``chip_smoke.py`` runs — no chip attached.

The TPU compiler is installed here and compiles for a topology that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2).
Interpret mode on CPU cannot see what it refuses: a DMA slice not
aligned to the HBM tiling, an SMEM block that disagrees with XLA's 1-D
layout, a block that outgrows VMEM — all three were found this way
(``bigdl_tpu/ops/PALLAS_NOTES.md``).  A compile that passes is not a
chip run; ``chip_smoke.py`` is.

Rules this file keeps (they are what makes it safe under xdist):
the topology is described inside a module-scoped fixture, never at
import, in a ``skipif`` or a ``parametrize`` argument; everything built
from it is built in fixtures/tests; ``interpret=False`` is passed or
patched here, not through an option of the program; the persistent
compile cache is off around the compiles; no child process; ONE file,
so one worker loads the TPU library.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bigdl_tpu import nn
from bigdl_tpu.ops import (pallas_embed, pallas_int8_gemm, pallas_lstm,
                           pallas_pool)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns
    and recompiles) — keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def _assert_kernel(compiled, n=1):
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= n, \
        f"expected >= {n} tpu_custom_call in the compiled program"


# ------------------------------------------------------------- LSTM cell
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_lstm_cell_ptb_medium(one_chip, dtype, grad):
    N, H = 20, 650
    assert pallas_lstm.supported(N, H, dtype)

    def cell(zx, h, c, w_t):
        return pallas_lstm.lstm_cell(zx, h, c, w_t, interpret=False)

    def loss(zx, h, c, w_t):
        h2, c2 = cell(zx, h, c, w_t)
        return (h2.astype(jnp.float32).sum()
                + c2.astype(jnp.float32).sum())

    fn = jax.grad(loss, argnums=(0, 1, 2, 3)) if grad else cell
    compiled = _compile(
        fn, _spec(one_chip, (N, 4 * H), dtype),
        _spec(one_chip, (N, H), dtype), _spec(one_chip, (N, H), dtype),
        _spec(one_chip, (H, 4 * H), dtype))
    _assert_kernel(compiled, 2 if grad else 1)


# ------------------------------------------------------------- int8 GEMM
@pytest.mark.parametrize("mode", pallas_int8_gemm.MODES)
def test_int8_matmul_256x2048x2048(one_chip, mode):
    N, K, O = 256, 2048, 2048
    assert pallas_int8_gemm.supported(N, K, O, jnp.float32, mode)

    def gemm(x, wq, ws, b):
        return pallas_int8_gemm.int8_matmul(
            x, wq, ws, b, mode=mode, impl="pallas", interpret=False)

    compiled = _compile(
        gemm, _spec(one_chip, (N, K), jnp.float32),
        _spec(one_chip, (O, K), jnp.int8),
        _spec(one_chip, (O,), jnp.float32),
        _spec(one_chip, (O,), jnp.float32))
    _assert_kernel(compiled)


# ---------------------------------------------------------- embedding bag
@pytest.mark.parametrize("table", [(100_000, 1), (10_000, 16),
                                   (10_000, 128)],
                         ids=["wide_d1", "embed_d16", "lane_d128"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_embedding_bag_census(one_chip, table, grad):
    nnz, n_rows = 65536, 8192
    assert pallas_embed.supported(nnz, n_rows, table, jnp.float32)

    def bag(rows, cols, vals, tab):
        return pallas_embed.embedding_bag_coo(rows, cols, vals, tab,
                                              n_rows, interpret=False)

    def loss(rows, cols, vals, tab):
        return (bag(rows, cols, vals, tab) ** 2).sum()

    fn = jax.grad(loss, argnums=(2, 3)) if grad else bag
    compiled = _compile(
        fn, _spec(one_chip, (nnz,), jnp.int32),
        _spec(one_chip, (nnz,), jnp.int32),
        _spec(one_chip, (nnz,), jnp.float32),
        _spec(one_chip, table, jnp.float32))
    _assert_kernel(compiled)


def test_embedding_bag_gate_matches_compiler(one_chip):
    """The classes ``supported()`` refuses are the ones the compiler
    refuses: a bf16 table (packed sublane pairs cannot be row-sliced),
    and an accumulator past the VMEM budget."""
    nnz, n_rows = 65536, 8192
    assert not pallas_embed.supported(nnz, n_rows, (10_000, 128),
                                      jnp.bfloat16)
    assert not pallas_embed.supported(nnz, 32768, (10_000, 128),
                                      jnp.float32)

    def bag(n):
        return lambda r, c, v, t: pallas_embed.embedding_bag_coo(
            r, c, v, t, n, interpret=False)

    streams = (_spec(one_chip, (nnz,), jnp.int32),
               _spec(one_chip, (nnz,), jnp.int32),
               _spec(one_chip, (nnz,), jnp.float32))
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(bag(n_rows), *streams,
                 _spec(one_chip, (10_000, 128), jnp.bfloat16))
    with pytest.raises(Exception, match="vmem"):
        _compile(bag(32768), *streams,
                 _spec(one_chip, (10_000, 128), jnp.float32))


# ----------------------------------------------------------- pool backward
def _pool_bwd_specs(one_chip, H, C, dtype=jnp.float32):
    N, OH = 2, H // 2
    return (_spec(one_chip, (N, H, H, C), dtype),
            _spec(one_chip, (N, OH, OH, C), dtype),
            _spec(one_chip, (N, OH, OH, C), dtype))


def _pool_bwd(x, y, g):
    return pallas_pool.maxpool_bwd_nhwc(x, y, g, (3, 3), (2, 2),
                                        ((1, 1), (1, 1)))


def test_pool_bwd_largest_admitted_block(one_chip):
    # 112 x 112 x 128 = 1,605,632 elements: the largest block under the
    # gate (the 802,816-element abort of an earlier toolchain is gone).
    # The refusal side — 224 x 224 x 64 runs out of VMEM — takes the
    # compiler 160 s to reach, so it was asked once (PALLAS_NOTES.md)
    # and only the gate's "no" is kept as a test (test_round5_closures)
    assert pallas_pool.supported((2, 112, 112, 128), (3, 3), (2, 2),
                                 ((1, 1), (1, 1)))
    _assert_kernel(_compile(_pool_bwd,
                            *_pool_bwd_specs(one_chip, 112, 128)))


# ------------------------------------------------------- one whole step
def test_ptb_medium_grad_step_has_fused_cell(one_chip, monkeypatch):
    """``ptb_model(10000, 650, 650, 2)`` fwd+bwd with the fused cell:
    the kernels survive inside the scan of a whole grad program."""
    from bigdl_tpu.models.rnn import ptb_model
    from bigdl_tpu.utils.precision import mixed_precision_loss_fn
    # the layer asks the backend (CPU here) whether to interpret —
    # steer it in the test, the program grows no option for this
    monkeypatch.setattr(pallas_lstm, "_interpret_default", lambda: False)
    model = ptb_model(10000, 650, 650, 2, kernel_impl="pallas")
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion())
    loss_fn = mixed_precision_loss_fn(model, crit, jnp.float32)
    params, mstate = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    as_spec = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    tokens = _spec(one_chip, (20, 35), jnp.int32)
    rng = _spec(one_chip, (2,), jnp.uint32)
    compiled = _compile(
        jax.value_and_grad(loss_fn, has_aux=True), as_spec(params),
        as_spec(mstate), tokens, tokens, rng)
    # fwd + bwd kernel of layer 0: MultiRNNCell hoists (and so fuses)
    # only its first cell — upper layers take Cell.step, the XLA chain
    _assert_kernel(compiled, 2)


# ------------------------------------------- the per-step output layer
# "name = type opcode(" of one HLO instruction; the type may be a tuple
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\(")
_HLO_SHAPE_RE = re.compile(r"\w+\[([\d,]*)\]")


def _results_of(hlo_text, n_elements):
    """``(opcode, line)`` of every instruction in ``hlo_text``, once for
    each array of ``n_elements`` in its result (a tuple's members
    counted)."""
    for line in hlo_text.splitlines():
        m = _HLO_INSTR_RE.match(line)
        if not m:
            continue
        for dims in _HLO_SHAPE_RE.findall(m.group(1)):
            if math.prod(int(d) for d in dims.split(",") if d) == n_elements:
                yield m.group(2), line.strip()[:120]


def _relayouts(hlo_text, n_elements):
    """The ``copy`` / ``reshape`` / ``transpose`` instructions of the
    module whose result holds ``n_elements``: each is a pass over that
    many elements that computes nothing (a reshape the compiler could
    make free is a ``bitcast`` by now)."""
    return [line for op, line in _results_of(hlo_text, n_elements)
            if op in ("copy", "reshape", "transpose")]


def _writers(hlo_text, n_elements):
    """The instructions of the entry computation that WRITE an array of
    ``n_elements``: what names a buffer and writes none is left out."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    return [line for op, line in _results_of(entry, n_elements)
            if op not in ("get-tuple-element", "bitcast", "tuple",
                          "parameter")]


_PTB_HEAD = (660, 35, 650, 10000)  # N, T, H, V of the benchmark's cell


def _compile_ptb_head(one_chip, activation, inner):
    """PTB-medium's head at the benchmark's batch, forward and backward:
    ``TimeDistributed(Linear)`` [-> row-wise activation] ->
    ``TimeDistributedCriterion(inner)``."""
    N, T, H, V = _PTB_HEAD
    model = nn.Sequential().add(nn.TimeDistributed(nn.Linear(H, V)))
    if activation is not None:
        model.add(activation())
    crit = nn.TimeDistributedCriterion(inner())

    def loss_fn(params, mstate, x, y):
        out, _ = model.apply(params, mstate, x, training=True)
        return crit.apply(out, y)

    params, mstate = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    as_spec = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    return _compile(
        jax.value_and_grad(loss_fn), as_spec(params), as_spec(mstate),
        _spec(one_chip, (N, T, H), jnp.float32),
        _spec(one_chip, (N, T), jnp.int32))


@pytest.mark.parametrize("head", [
    (nn.LogSoftMax, nn.ClassNLLCriterion),
    (nn.SoftMax, nn.CategoricalCrossEntropy),
], ids=["LogSoftMax", "SoftMax"])
def test_time_distributed_head_keeps_one_logits_layout(one_chip, head):
    """The 924 MB of logits stay (N*T, V) from the matmul to the loss
    and back; with the activation on the (N, T, V) array the compiler
    re-laid them out four times a step (35 rows pad to 40 in the
    (8, 128) tiling), 23 % of the device's time on the chip (PERF.md,
    PR 29)."""
    N, T, _, V = _PTB_HEAD
    compiled = _compile_ptb_head(one_chip, *head)
    assert _relayouts(compiled.as_text(), N * T * V) == []


@pytest.mark.parametrize("head", [
    (nn.LogSoftMax, nn.ClassNLLCriterion),
    (None, nn.CrossEntropyCriterion),
], ids=["LogSoftMax+ClassNLL", "CrossEntropy"])
def test_head_writes_the_logits_and_nothing_else_that_large(one_chip, head):
    """The matmul's result is the ONE logits-sized array of the head:
    the log-probabilities are an operand of the class pick's row sum
    (``nn.criterion._pick_class``), written nowhere.  Picked by a
    gather they were materialised for it, 924 MB a step and 9 % of the
    device's time on the chip (PERF.md, PR 31), under either spelling:
    ``CrossEntropyCriterion`` compiled to the same program."""
    N, T, _, V = _PTB_HEAD
    compiled = _compile_ptb_head(one_chip, *head)
    assert len(_writers(compiled.as_text(), N * T * V)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1e9


# ------------------------- granite-4.0-h-small: one chip's share (PR 32)
# The layers of ``benchmarks/configs/granite-4.0-h-small-share8.json`` at
# the published widths and the cell's 8,192 tokens, forward and backward
# in bf16.  They hold no Pallas call of this repo's; what is guarded is
# what the cell's sizing rests on: that the TPU compiler still lowers
# ``ragged_dot`` to its own grouped-matmul kernels (an expansion to dense
# masked products would cost 9x the operations), and that no layer holds
# a buffer the size of what blocking and chunking exist to avoid.
GRANITE_T, GRANITE_D = 8192, 4096


def _granite_grad(module, one_chip, monkeypatch=None):
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0))
    params, state = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, jnp.bfloat16
                        if a.dtype == jnp.float32 else a.dtype), shapes)

    def loss(p, x):
        y, _ = module.apply(p, jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), state), x)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    return _compile(jax.grad(loss, argnums=(0, 1)), params,
                    _spec(one_chip, (1, GRANITE_T, GRANITE_D),
                          jnp.bfloat16))


def test_granite_expert_layer_keeps_the_grouped_kernels(one_chip):
    moe = nn.ExpertParallelMoE(GRANITE_D, 768, 72, 10, held=(0, 9))
    assert moe.n_rows(GRANITE_T) == 15360
    compiled = _granite_grad(moe, one_chip)
    text = compiled.as_text()
    # forward twice (w_in, w_out), and for each a product for dx and one
    # for dw: six grouped kernels, none expanded
    assert len(re.findall(r"= \S+ custom-call\([^\n]*ragged_dot_tiling",
                          text)) >= 6
    # ... and each of them, with the metadata call beside it, is placed
    # under ``bigdl.moe.experts`` by the prefix the benchmark's builder
    # gives (``COMPILER_OPS``): the compiler names them itself and
    # drops the scope.  A compiler that renames them fails HERE, not as
    # a ``moe.device_share`` silently 0.2 too low on the chip
    from benchmarks import hlo_scopes, lib
    placed = hlo_scopes.instruction_scopes(
        text, lib.load_module("builders",
                              "granite_moe_hybrid").COMPILER_OPS)
    assert hlo_scopes.unscoped_kernels(text, placed) == []
    assert len(hlo_scopes.unscoped_kernels(
        text, hlo_scopes.instruction_scopes(text))) >= 6
    # the largest buffers are R x 4096 rows and T x 4096 tokens, never
    # the T x k x 4096 = 671 MB of a per-assignment gather
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_granite_mamba_mixer_chunked_scan_fits(one_chip):
    mixer = nn.Mamba2Mixer(GRANITE_D, 128, 64, 128, held=(0, 16),
                           chunk_size=256)
    compiled = _granite_grad(mixer, one_chip)
    # 16 heads x 32 chunks x 256 x 256 decays are 134 MB in f32; a
    # T x T form of the scan would be 4.3 GB a head
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_granite_attention_never_holds_all_scores(one_chip):
    attn = nn.GroupedQueryAttention(GRANITE_D, 32, 8, 128, held=(0, 1),
                                    scale=0.0078125, q_block=1024)
    compiled = _granite_grad(attn, one_chip)
    # all scores of the 4 heads held: 4 x 8192 x 8192 x 4 = 1.07 GB in
    # f32; a block of 1,024 queries holds an eighth of that
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9
    assert "f32[1,4,8192,8192]" not in compiled.as_text()


class _CheckpointedLayers(nn.Module):
    """Granite layers stacked as ``GraniteMoeHybrid.apply`` stacks them:
    each under the model's own checkpoint."""

    def __init__(self, layers):
        super().__init__("CheckpointedLayers")
        self.layers = layers

    def init(self, rng):
        made = [m.init(rng) for m in self.layers]
        return [p for p, _ in made], [s for _, s in made]

    def apply(self, params, state, input, *, training=False, rng=None):
        from bigdl_tpu.models.share import checkpointed
        h = input
        for m, p, s in zip(self.layers, params, state):
            h, _ = checkpointed(m)(p, s, h)
        return h, state


def test_granite_layers_checkpoint_keeps_the_up_projections(one_chip):
    """A Mamba and an attention layer of the cell, stacked and
    checkpointed as the model does it: the backward makes no named
    up-projection a second time.  These counts are what the gain of
    PR 33 rests on (PERF.md 6); under a bare ``jax.checkpoint`` the same
    compile reads 16 grouped kernels, four of them ``rows W_in``, and
    four shared-expert up-projections."""
    from benchmarks import hlo_scopes, lib
    from bigdl_tpu.models.granite_moe_hybrid import GraniteMoeHybridLayer
    builder = lib.load_module("builders", "granite_moe_hybrid")
    cfg = lib.load_json("configs", "granite-4.0-h-small-share8")
    whole, share = builder.whole_config(cfg), builder.share(cfg)
    compiled = _granite_grad(_CheckpointedLayers(
        [GraniteMoeHybridLayer(whole, kind, share)
         for kind in ("mamba", "attention")]), one_chip)
    text = compiled.as_text()

    # the compiler names its grouped kernels itself: count them by shape.
    # Seven a layer: forward 2, backward 4, and ``ys`` made a second
    # time from the kept ``moe.h``; ``rows W_in`` in the forward alone
    grouped = re.findall(r"= (\S+) custom-call\([^\n]*ragged_dot_tiling",
                         text)
    assert len(grouped) == 14
    assert sum(r.startswith("bf16[15360,1536]") for r in grouped) == 2
    # the shared expert's up-projection, once a layer
    assert len(re.findall(r"= bf16\[8192,3072\]\S* convolution\(",
                          text)) == 2
    # (the gathered rows are not kept, models/granite_moe_hybrid.py says
    # why: a layer still gathers them twice, and no count holds that)
    placed = hlo_scopes.instruction_scopes(text, builder.COMPILER_OPS)
    assert hlo_scopes.unscoped_kernels(text, placed) == []
    # read 1,282,366,464 bytes (v5e:2x2 compile, PR 33): the kept arrays
    # of two layers are 0.20 GB of it
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# ------------------------------------ ZAYA1-8B: one chip's share (PR 35)
def test_zaya_layers_keep_the_grouped_kernels_and_no_full_scores(one_chip):
    """Two layers of ``benchmarks/configs/zaya1-8b-share2.json`` at the
    published widths and the cell's two records of 8,192 tokens, stacked
    and checkpointed as the model does it, forward and backward in bf16
    with the router's state crossing the checkpoints in f32: the expert
    layer behind the MLP router still lowers to the compiler's grouped
    kernels, seven a layer with ``rows W_in`` made once (granite's
    counts: the router is a part, the path is one), every kernel lies
    under a named scope, and attention in the latent never holds the
    scores of all 8,192 queries."""
    from benchmarks import hlo_scopes, lib
    from bigdl_tpu.models.share import checkpointed
    from bigdl_tpu.models.zaya import ZayaLayer
    builder = lib.load_module("builders", "zaya")
    cfg = lib.load_json("configs", "zaya1-8b-share2")
    whole, share = builder.whole_config(cfg), builder.share(cfg)
    layers = [ZayaLayer(whole, share, first=j == 0,
                        row_factor=cfg["train"]["row_factor"])
              for j in range(2)]
    assert layers[0].experts.n_rows(2 * 8192) == 16384
    shapes = [jax.eval_shape(m.init, jax.random.PRNGKey(0)) for m in layers]
    params = [jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, jnp.bfloat16), p)
        for p, _ in shapes]

    def loss(ps, h, r):
        for m, p, (_, s) in zip(layers, ps, shapes):
            state = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), s)
            (h, r), _ = checkpointed(m)(p, state, (h, r))
        return jnp.sum(h.astype(jnp.float32) ** 2) + jnp.sum(r ** 2)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), params,
                        _spec(one_chip, (2, 8192, 2048), jnp.bfloat16),
                        _spec(one_chip, (2, 8192, 256), jnp.float32))
    text = compiled.as_text()
    grouped = re.findall(r"= (\S+) custom-call\([^\n]*ragged_dot_tiling",
                         text)
    assert len(grouped) == 14
    assert sum(r.startswith("bf16[16384,4096]") for r in grouped) == 2
    placed = hlo_scopes.instruction_scopes(text, builder.COMPILER_OPS)
    assert hlo_scopes.unscoped_kernels(text, placed) == []
    assert {"bigdl.cca.project", "bigdl.cca.mix", "bigdl.cca.attend",
            "bigdl.cca.out", "bigdl.moe.route"} <= set(placed.values())
    # all scores of the 4 heads held of 2 records: 2 x 4 x 8192 x 8192
    # x 4 = 2.1 GB in f32; a block of 1,024 queries holds an eighth
    assert not re.search(r"f32\[[0-9,]*8192,8192\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9

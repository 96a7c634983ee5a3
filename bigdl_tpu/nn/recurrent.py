"""Recurrent stack.

Reference: ``DL/nn/Recurrent.scala`` (855 LoC) unrolls a ``Cell`` over time
with cloned cells sharing weights; ``RecurrentDecoder`` feeds output back as
input; plus ``RnnCell``/``LSTM``/``LSTMPeephole``/``GRU``/
``ConvLSTMPeephole``/``MultiRNNCell``/``BiRecurrent``/``TimeDistributed``.

TPU redesign: **unrolling becomes ``lax.scan``** — one compiled step body,
weights naturally shared, sequence dim handled by XLA (no cloned cells, no
hidden-state plumbing between mutable modules).  This is the SURVEY §7 risk
item "Recurrent/dynamic shapes under XLA": static max-length sequences +
masking, never data-dependent python loops.

Layout: batch-major ``(N, T, features)`` like the reference's default
(batchNormParams aside).  Cells are stateless modules whose ``apply`` takes
``(x_t, hidden)`` packed as a tuple and returns ``(out_t, new_hidden)``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.initialization import RandomUniform, InitializationMethod


def _cast_hidden(hidden, dtype):
    """Match the hidden state to the input dtype so bf16 mixed precision
    flows through the scan (an f32 hidden would promote every step's
    concat/matmul back to f32, silently disabling the MXU speedup)."""
    if not jnp.issubdtype(dtype, jnp.floating):
        return hidden
    return jax.tree_util.tree_map(lambda h: h.astype(dtype), hidden)


class Cell(Module):
    """Recurrent cell contract: ``step(params, x_t, hidden) -> (y_t, hidden)``
    plus ``initial_hidden(batch)``."""

    hidden_size: int

    def initial_hidden(self, batch_size: int):
        raise NotImplementedError

    def step(self, params, x_t, hidden):
        raise NotImplementedError

    # -- optional scan optimization (TPU) -------------------------------
    # The input-side projection x_t @ W_x has no sequential dependency,
    # so a cell may expose it for hoisting: ``Recurrent`` then computes
    # it for ALL timesteps as ONE large MXU-efficient matmul
    # ((T*N, D) @ (D, 4H)) and the scan body keeps only the h-side
    # matmul — roughly halving the work trapped inside the sequential
    # loop, which is where small-batch RNNs spend their time on TPU.
    # Numerics: x@Wx + h@Wh sums the D and H reduction axes separately
    # instead of as one (D+H) reduction — a reassociation within normal
    # float tolerance of the fused form.

    def hoist(self, params, xs):
        """Precompute the input projections for a (T, N, ...) sequence;
        return the per-step pytree to scan over, or None when this cell
        has no hoistable form (the default)."""
        return None

    def step_hoisted(self, params, zx_t, hidden):
        """``step`` consuming a :meth:`hoist` slice instead of x_t."""
        raise NotImplementedError

    # a Cell used standalone acts on one timestep: input=(x_t, hidden)
    def apply(self, params, state, input, *, training=False, rng=None):
        x_t, hidden = input
        y, new_hidden = self.step(params, x_t, hidden)
        return (y, new_hidden), state


def _uniform(rng, shape, fan_in):
    return RandomUniform().init(rng, shape, fan_in, fan_in)


class RnnCell(Cell):
    """Elman RNN: h' = act(W x + U h + b) (reference ``RNN.scala``
    RnnCell; default Tanh activation)."""

    def __init__(self, input_size: int, hidden_size: int,
                 activation=jnp.tanh, name: Optional[str] = None):
        super().__init__(name)
        self.input_size, self.hidden_size = input_size, hidden_size
        self.activation = activation

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        fan = self.input_size + self.hidden_size
        return {"w_ih": _uniform(k1, (self.hidden_size, self.input_size), fan),
                "w_hh": _uniform(k2, (self.hidden_size, self.hidden_size), fan),
                "bias": _uniform(k3, (self.hidden_size,), fan)}, {}

    def initial_hidden(self, batch_size: int):
        return jnp.zeros((batch_size, self.hidden_size), jnp.float32)

    def step(self, params, x_t, h):
        h_new = self.activation(x_t @ params["w_ih"].T + h @ params["w_hh"].T
                                + params["bias"])
        return h_new, h_new

    def hoist(self, params, xs):
        return xs @ params["w_ih"].T + params["bias"]

    def step_hoisted(self, params, zx_t, h):
        h_new = self.activation(zx_t + h @ params["w_hh"].T)
        return h_new, h_new


class LSTM(Cell):
    """LSTM cell (reference ``LSTM.scala``): gates i,f,g,o from one fused
    projection of [x, h] — a single MXU matmul per step.

    ``impl`` selects the scan-body cell kernel for the hoisted path:
    ``None`` defers to ``Engine.kernel_impl()`` (``Config.kernel_impl``
    / ``BIGDL_TPU_KERNEL_IMPL``), ``"pallas"`` opts into the fused
    VMEM-resident cell (``ops/pallas_lstm.py`` — recurrent matmul with
    f32 accumulation + all four gates + cell/hidden update in one pass,
    replacing this chain of per-op HBM round-trips), ``"xla"`` pins the
    baseline lowering.  Unsupported shapes silently take the XLA path
    (``pallas_lstm.supported``); parity is gated in
    ``tests/test_pallas_kernels.py``."""

    def __init__(self, input_size: int, hidden_size: int,
                 forget_bias: float = 0.0, name: Optional[str] = None,
                 impl: Optional[str] = None):
        super().__init__(name)
        self.input_size, self.hidden_size = input_size, hidden_size
        self.forget_bias = forget_bias
        self.impl = impl

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        H, D = self.hidden_size, self.input_size
        fan = D + H
        w = _uniform(k1, (4 * H, D + H), fan)
        b = _uniform(k2, (4 * H,), fan)
        return {"weight": w, "bias": b}, {}

    def initial_hidden(self, batch_size: int):
        H = self.hidden_size
        return (jnp.zeros((batch_size, H), jnp.float32),
                jnp.zeros((batch_size, H), jnp.float32))

    def step(self, params, x_t, hidden):
        h, c = hidden
        z = jnp.concatenate([x_t, h], axis=-1) @ params["weight"].T \
            + params["bias"]
        return self._gates(z, c)

    def _gates(self, z, c):
        i, f, g, o = jnp.split(z, 4, axis=-1)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f + self.forget_bias)
        g = jnp.tanh(g)
        o = jax.nn.sigmoid(o)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        return h_new, (h_new, c_new)

    def hoist(self, params, xs):
        D = self.input_size
        return xs @ params["weight"][:, :D].T + params["bias"]

    def step_hoisted(self, params, zx_t, hidden):
        h, c = hidden
        if self._fused_cell_engaged(h):
            from bigdl_tpu.ops.pallas_lstm import lstm_cell
            # (H, 4H) transposed recurrent slice; loop-invariant, so
            # XLA hoists the transpose out of the scan
            w_t = params["weight"][:, self.input_size:].T
            h_new, c_new = lstm_cell(zx_t, h, c, w_t,
                                     forget_bias=self.forget_bias)
            return h_new, (h_new, c_new)
        # the loop-invariant W_h slice is hoisted out of the scan by
        # XLA's while-loop invariant code motion
        z = zx_t + h @ params["weight"][:, self.input_size:].T
        return self._gates(z, c)

    def _fused_cell_engaged(self, h) -> bool:
        """Static (trace-time) kernel choice: resolved impl says pallas
        AND the measured supported() gate passes for this shape/dtype —
        anything else silently keeps the XLA chain."""
        from bigdl_tpu.ops import pallas_lstm, resolve_kernel_impl
        if resolve_kernel_impl(self.impl) != "pallas":
            return False
        return pallas_lstm.supported(h.shape[0], self.hidden_size,
                                     h.dtype.type)


class LSTMPeephole(Cell):
    """LSTM with peephole connections (reference ``LSTMPeephole.scala``)."""

    def __init__(self, input_size: int, hidden_size: int,
                 name: Optional[str] = None):
        super().__init__(name)
        self.input_size, self.hidden_size = input_size, hidden_size

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        H, D = self.hidden_size, self.input_size
        fan = D + H
        return {"weight": _uniform(k1, (4 * H, D + H), fan),
                "bias": _uniform(k2, (4 * H,), fan),
                "peep": _uniform(k3, (3, H), fan)}, {}

    def initial_hidden(self, batch_size: int):
        H = self.hidden_size
        return (jnp.zeros((batch_size, H), jnp.float32),
                jnp.zeros((batch_size, H), jnp.float32))

    def step(self, params, x_t, hidden):
        h, c = hidden
        z = jnp.concatenate([x_t, h], axis=-1) @ params["weight"].T \
            + params["bias"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        p = params["peep"]
        i = jax.nn.sigmoid(i + p[0] * c)
        f = jax.nn.sigmoid(f + p[1] * c)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        o = jax.nn.sigmoid(o + p[2] * c_new)
        h_new = o * jnp.tanh(c_new)
        return h_new, (h_new, c_new)


class GRU(Cell):
    """GRU cell (reference ``GRU.scala``)."""

    def __init__(self, input_size: int, hidden_size: int,
                 name: Optional[str] = None):
        super().__init__(name)
        self.input_size, self.hidden_size = input_size, hidden_size

    def init(self, rng):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        H, D = self.hidden_size, self.input_size
        fan = D + H
        return {"w_gates": _uniform(k1, (2 * H, D + H), fan),
                "b_gates": _uniform(k2, (2 * H,), fan),
                "w_cand": _uniform(k3, (H, D + H), fan),
                "b_cand": _uniform(k4, (H,), fan)}, {}

    def initial_hidden(self, batch_size: int):
        return jnp.zeros((batch_size, self.hidden_size), jnp.float32)

    def step(self, params, x_t, h):
        z = jnp.concatenate([x_t, h], axis=-1) @ params["w_gates"].T \
            + params["b_gates"]
        r, u = jnp.split(jax.nn.sigmoid(z), 2, axis=-1)
        cand = jnp.tanh(jnp.concatenate([x_t, r * h], axis=-1)
                        @ params["w_cand"].T + params["b_cand"])
        h_new = u * h + (1 - u) * cand
        return h_new, h_new

    def hoist(self, params, xs):
        D = self.input_size
        return (xs @ params["w_gates"][:, :D].T + params["b_gates"],
                xs @ params["w_cand"][:, :D].T + params["b_cand"])

    def step_hoisted(self, params, zx_t, h):
        zg, zc = zx_t
        D = self.input_size
        z = zg + h @ params["w_gates"][:, D:].T
        r, u = jnp.split(jax.nn.sigmoid(z), 2, axis=-1)
        cand = jnp.tanh(zc + (r * h) @ params["w_cand"][:, D:].T)
        h_new = u * h + (1 - u) * cand
        return h_new, h_new


class ConvLSTMPeephole(Cell):
    """Convolutional LSTM over NCHW feature maps (reference
    ``ConvLSTMPeephole.scala``).

    ``with_peephole=True`` adds the reference's per-channel peephole
    terms (Wci/Wcf/Wco elementwise on the cell state) and is the
    default, matching the reference's ``withPeephole=true``;
    ``False`` is the plain ConvLSTM variant (its
    ``withPeephole=false`` mode)."""

    def __init__(self, input_size: int, output_size: int, kernel: int = 3,
                 spatial: Optional[tuple[int, int]] = None,
                 with_peephole: bool = True,
                 name: Optional[str] = None):
        super().__init__(name)
        self.input_size, self.output_size = input_size, output_size
        self.kernel = kernel
        self.spatial = spatial  # (H, W), required for initial_hidden
        self.hidden_size = output_size
        self.with_peephole = with_peephole

    def init(self, rng):
        # split(2) when peephole-free so earlier rounds' seeded init
        # streams are preserved exactly
        if self.with_peephole:
            k1, k2, k3 = jax.random.split(rng, 3)
        else:
            k1, k2 = jax.random.split(rng)
        C_in, C_out, K = self.input_size, self.output_size, self.kernel
        fan = (C_in + C_out) * K * K
        w = _uniform(k1, (4 * C_out, C_in + C_out, K, K), fan)
        b = _uniform(k2, (4 * C_out,), fan)
        params = {"weight": w, "bias": b}
        if self.with_peephole:
            # per-channel Wci/Wcf/Wco (reference peephole CMuls)
            params["peep"] = _uniform(k3, (3, C_out), fan)
        return params, {}

    def initial_hidden(self, batch_size: int):
        assert self.spatial is not None, \
            "ConvLSTMPeephole needs spatial=(H, W) for initial hidden"
        H, W = self.spatial
        shape = (batch_size, self.output_size, H, W)
        return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))

    def step(self, params, x_t, hidden):
        if self.with_peephole and "peep" not in params:
            raise KeyError(
                "ConvLSTMPeephole now defaults to with_peephole=True "
                "(the reference default); these params have no 'peep' "
                "entry — construct with with_peephole=False to restore "
                "a peephole-free checkpoint")
        h, c = hidden
        z = lax.conv_general_dilated(
            jnp.concatenate([x_t, h], axis=1), params["weight"],
            window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        z = z + params["bias"][None, :, None, None]
        i, f, g, o = jnp.split(z, 4, axis=1)
        if self.with_peephole:
            p = params["peep"][:, None, :, None, None]
            i = i + p[0] * c
            f = f + p[1] * c
        c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        if self.with_peephole:
            o = o + params["peep"][2][None, :, None, None] * c_new
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        return h_new, (h_new, c_new)


class ConvLSTMPeephole3D(Cell):
    """Volumetric ConvLSTM over NCDHW feature maps (reference
    ``ConvLSTMPeephole3D.scala``; 3-D twin of :class:`ConvLSTMPeephole`,
    including the ``withPeephole=true`` reference default)."""

    def __init__(self, input_size: int, output_size: int, kernel: int = 3,
                 spatial: Optional[tuple[int, int, int]] = None,
                 with_peephole: bool = True,
                 name: Optional[str] = None):
        super().__init__(name)
        self.input_size, self.output_size = input_size, output_size
        self.kernel = kernel
        self.spatial = spatial  # (D, H, W), required for initial_hidden
        self.hidden_size = output_size
        self.with_peephole = with_peephole

    def init(self, rng):
        if self.with_peephole:
            k1, k2, k3 = jax.random.split(rng, 3)
        else:
            k1, k2 = jax.random.split(rng)
        C_in, C_out, K = self.input_size, self.output_size, self.kernel
        fan = (C_in + C_out) * K * K * K
        w = _uniform(k1, (4 * C_out, C_in + C_out, K, K, K), fan)
        b = _uniform(k2, (4 * C_out,), fan)
        params = {"weight": w, "bias": b}
        if self.with_peephole:
            params["peep"] = _uniform(k3, (3, C_out), fan)
        return params, {}

    def initial_hidden(self, batch_size: int):
        assert self.spatial is not None, \
            "ConvLSTMPeephole3D needs spatial=(D, H, W) for initial hidden"
        D, H, W = self.spatial
        shape = (batch_size, self.output_size, D, H, W)
        return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))

    def step(self, params, x_t, hidden):
        if self.with_peephole and "peep" not in params:
            raise KeyError(
                "ConvLSTMPeephole3D now defaults to with_peephole=True "
                "(the reference default); these params have no 'peep' "
                "entry — construct with with_peephole=False to restore "
                "a peephole-free checkpoint")
        h, c = hidden
        z = lax.conv_general_dilated(
            jnp.concatenate([x_t, h], axis=1), params["weight"],
            window_strides=(1, 1, 1), padding="SAME",
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
        z = z + params["bias"][None, :, None, None, None]
        i, f, g, o = jnp.split(z, 4, axis=1)
        if self.with_peephole:
            p = params["peep"][:, None, :, None, None, None]
            i = i + p[0] * c
            f = f + p[1] * c
        c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        if self.with_peephole:
            o = o + params["peep"][2][None, :, None, None, None] * c_new
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        return h_new, (h_new, c_new)


class MultiRNNCell(Cell):
    """Stack cells vertically (reference ``MultiRNNCell.scala``)."""

    def __init__(self, cells: Sequence[Cell], name: Optional[str] = None):
        super().__init__(name)
        self.cells = list(cells)
        self.hidden_size = self.cells[-1].hidden_size

    def spec_children(self):
        return {str(i): c for i, c in enumerate(self.cells)}

    def init(self, rng):
        params = {}
        for i, c in enumerate(self.cells):
            rng, sub = jax.random.split(rng)
            p, _ = c.init(sub)
            params[str(i)] = p
        return params, {}

    def initial_hidden(self, batch_size: int):
        return tuple(c.initial_hidden(batch_size) for c in self.cells)

    def step(self, params, x_t, hidden):
        new_hidden = []
        out = x_t
        for i, c in enumerate(self.cells):
            out, h = c.step(params[str(i)], out, hidden[i])
            new_hidden.append(h)
        return out, tuple(new_hidden)

    def hoist(self, params, xs):
        # only layer 0 sees the raw sequence; deeper layers consume
        # in-loop outputs, so their projections cannot move out.
        # getattr: layer 0 may be a duck-typed/quantized cell without
        # the hoist API (same contract as Recurrent.apply)
        h0 = getattr(self.cells[0], "hoist", None)
        return h0(params["0"], xs) if h0 is not None else None

    def step_hoisted(self, params, zx_t, hidden):
        new_hidden = []
        out, h = self.cells[0].step_hoisted(params["0"], zx_t, hidden[0])
        new_hidden.append(h)
        for i, c in enumerate(self.cells[1:], start=1):
            out, h = c.step(params[str(i)], out, hidden[i])
            new_hidden.append(h)
        return out, tuple(new_hidden)


class Recurrent(Module):
    """Run a Cell over the time dim of (N, T, ...) via ``lax.scan``
    (reference ``Recurrent.scala``; returns the full output sequence).

    TPU scan discipline: the input-side projections are hoisted out of
    the loop when the cell supports it (see :meth:`Cell.hoist` — one
    large MXU matmul replaces T small ones), and ``unroll`` is passed to
    ``lax.scan`` — small-batch RNN steps are dispatch-bound on TPU, so
    unrolling the loop body amortizes per-iteration overhead (a
    round-5 chip capture at batch 20, before the ledger: PERF.md
    section 7, "decisions from chip captures before the ledger").  Both
    are exact-math transformations (hoisting reassociates one float
    reduction)."""

    def __init__(self, cell: Cell, reverse: bool = False,
                 unroll: int = 1, name: Optional[str] = None):
        super().__init__(name)
        self.cell = cell
        self.reverse = reverse
        self.unroll = unroll

    def spec_children(self):
        return self.cell

    def init(self, rng):
        return self.cell.init(rng)

    def apply(self, params, state, input, *, training=False, rng=None):
        N = input.shape[0]
        hidden0 = _cast_hidden(self.cell.initial_hidden(N), input.dtype)
        xs = jnp.moveaxis(input, 1, 0)  # (T, N, ...) scan-major
        if self.reverse:
            xs = jnp.flip(xs, axis=0)

        # duck-typed: any object with step/initial_hidden is a valid
        # cell (quantized cells, user cells predating the hoist API)
        hoist = getattr(self.cell, "hoist", None)
        zx = hoist(params, xs) if hoist is not None else None
        if zx is not None:
            def body(hidden, zx_t):
                y, new_hidden = self.cell.step_hoisted(params, zx_t,
                                                       hidden)
                return new_hidden, y
            _, ys = lax.scan(body, hidden0, zx, unroll=self.unroll)
        else:
            def body(hidden, x_t):
                y, new_hidden = self.cell.step(params, x_t, hidden)
                return new_hidden, y
            _, ys = lax.scan(body, hidden0, xs, unroll=self.unroll)
        if self.reverse:
            ys = jnp.flip(ys, axis=0)
        return jnp.moveaxis(ys, 0, 1), state  # back to (N, T, ...)


class BiRecurrent(Module):
    """Bidirectional wrapper (reference ``BiRecurrent.scala``; merge =
    concat on the feature dim by default, or 'add')."""

    def __init__(self, cell_fwd: Cell, cell_bwd: Optional[Cell] = None,
                 merge: str = "concat", name: Optional[str] = None):
        super().__init__(name)
        import copy
        self.fwd = Recurrent(cell_fwd)
        self.bwd = Recurrent(cell_bwd if cell_bwd is not None
                             else copy.deepcopy(cell_fwd), reverse=True)
        self.merge = merge

    def spec_children(self):
        return {"fwd": self.fwd, "bwd": self.bwd}

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        pf, _ = self.fwd.init(k1)
        pb, _ = self.bwd.init(k2)
        return {"fwd": pf, "bwd": pb}, {}

    def apply(self, params, state, input, *, training=False, rng=None):
        yf, _ = self.fwd.apply(params["fwd"], {}, input, training=training)
        yb, _ = self.bwd.apply(params["bwd"], {}, input, training=training)
        if self.merge == "concat":
            return jnp.concatenate([yf, yb], axis=-1), state
        return yf + yb, state


class RecurrentDecoder(Module):
    """Decode ``seq_length`` steps feeding each output back as the next
    input (reference ``RecurrentDecoder.scala``).  Input: the first-step
    input (N, features)."""

    def __init__(self, cell: Cell, seq_length: int,
                 name: Optional[str] = None):
        super().__init__(name)
        self.cell = cell
        self.seq_length = seq_length

    def spec_children(self):
        return self.cell

    def init(self, rng):
        return self.cell.init(rng)

    def apply(self, params, state, input, *, training=False, rng=None):
        N = input.shape[0]
        hidden0 = _cast_hidden(self.cell.initial_hidden(N), input.dtype)

        def body(carry, _):
            x, hidden = carry
            y, new_hidden = self.cell.step(params, x, hidden)
            return (y, new_hidden), y

        _, ys = lax.scan(body, (input, hidden0), None,
                         length=self.seq_length)
        return jnp.moveaxis(ys, 0, 1), state


class TimeDistributed(Module):
    """Apply an inner module independently at each timestep of (N, T, ...)
    (reference ``TimeDistributed.scala``) by folding time into batch —
    XLA sees one big batched op instead of T small ones."""

    def __init__(self, layer: Module, name: Optional[str] = None):
        super().__init__(name)
        self.layer = layer

    def spec_children(self):
        return self.layer

    def init(self, rng):
        return self.layer.init(rng)

    def apply(self, params, state, input, *, training=False, rng=None):
        N, T = input.shape[0], input.shape[1]
        flat = input.reshape((N * T,) + input.shape[2:])
        out, new_state = self.layer.apply(params, state, flat,
                                          training=training, rng=rng)
        return out.reshape((N, T) + out.shape[1:]), new_state

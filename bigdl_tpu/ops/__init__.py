"""bigdl_tpu.ops — forward-only TF op execution layer.

Reference: ``DL/nn/ops/`` (71 files) + ``DL/nn/tf/`` (18 files): each TF
op the importer can meet is a forward-only ``Operation`` module executing
Torch-tensor math.  TPU redesign: an op is a pure function
``(attrs, *input_arrays) -> array`` registered by TF op name — the
imported graph executes as ONE jit-traced composition of these, so XLA
fuses the whole imported model instead of interpreting op-by-op.

The op set is scoped to what the importer needs for the benchmark-model
graphs (SURVEY §7 stage 10: "only as far as the TF importer needs"),
and grows with it.
"""

from bigdl_tpu.ops.registry import OPS, register_op, get_op


def resolve_kernel_impl(override=None) -> str:
    """Resolve the effective custom-kernel backend: ``"pallas"`` or
    ``"xla"``.

    Per-layer ``impl=`` override wins; otherwise ``Engine.kernel_impl()``
    (explicit ``Engine.set_kernel_impl`` > ``Config.kernel_impl``).
    ``"auto"`` means pallas-if-supported on a TPU backend and xla
    elsewhere — interpret-mode kernels are correctness emulation, not a
    speedup, so auto never engages them on CPU hosts (force with
    ``"pallas"``, which the tests do).  Runs at trace time on the host
    — the choice is static per compiled program."""
    from bigdl_tpu.engine import Engine
    impl = override if override is not None else Engine.kernel_impl()
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"kernel impl must be auto|pallas|xla, got {impl!r}")
    if impl == "auto":
        import jax
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


__all__ = ["OPS", "register_op", "get_op", "resolve_kernel_impl"]

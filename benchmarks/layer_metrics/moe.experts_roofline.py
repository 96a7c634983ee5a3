"""The held experts' grouped products against the chip's bf16 peak:
the operations forward and backward NEED for the assignments the model
counted (``rows_held`` a step, from the counter and not from the
buffer's size; the builder's operations per assignment; what remat
recomputes counts nothing), over the device time under
``bigdl.moe.experts`` a step, over the published peak.  Compute bounds
it: a row's 56.6 MFLOP move 12 KB of activations."""

from benchmarks import hlo_scopes


def read(obs):
    c = obs.get("moe_counters") or {}
    seconds = hlo_scopes.seconds_under(obs, "bigdl.moe.experts")
    if (not obs.get("peaks") or not obs.get("trace_steps")
            or not c.get("steps") or seconds is None):
        return None
    flops_per_step = c["rows_held"] / c["steps"] * c["flops_per_row"]
    seconds_per_step = seconds / obs["trace_steps"]
    return 100.0 * flops_per_step / seconds_per_step \
        / obs["peaks"]["bf16_flops_per_s"]

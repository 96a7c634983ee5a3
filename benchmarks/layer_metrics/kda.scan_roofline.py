"""The delta rule with a decay a key channel against the chip's
roofline: the least time the recurrence NEEDS a step (the builder's
``kda_counts``: its three key x value products and the state's decay a
token and head, and its f32 inputs and output once forward and three
times backward, whatever computes it; the larger of operations over the
bf16 peak and bytes over the HBM bandwidth), over the device time under
``bigdl.kda.scan`` a step.  At Ling-3.0-flash's widths the bytes bound
it: a token's 7 x 128 x 128 operations a head come with 641 f32 words."""

from benchmarks import hlo_scopes


def read(obs):
    c = obs.get("kda_counts") or {}
    peaks = obs.get("peaks")
    seconds = hlo_scopes.seconds_under(obs, "bigdl.kda.scan")
    if (not peaks or not obs.get("trace_steps")
            or not c.get("scan_flops_per_step") or seconds is None):
        return None
    least = max(c["scan_flops_per_step"] / peaks["bf16_flops_per_s"],
                c["scan_bytes_per_step"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / obs["trace_steps"])

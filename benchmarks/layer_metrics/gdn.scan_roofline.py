"""The gated delta rule against the chip's roofline: the least time the
recurrence NEEDS a step (the builder's ``gdn_counts``: its three
key x value products a token and head, and its f32 inputs and output
once forward and three times backward, whatever computes it; the larger
of operations over the bf16 peak and bytes over the HBM bandwidth),
over the device time under ``bigdl.gdn.scan`` a step.  At Qwen3-Next's
widths the bytes bound it: a token's 3 x 128 x 128 multiply-accumulates
a head come with 514 f32 words."""

from benchmarks import hlo_scopes


def read(obs):
    c = obs.get("gdn_counts") or {}
    peaks = obs.get("peaks")
    seconds = hlo_scopes.seconds_under(obs, "bigdl.gdn.scan")
    if (not peaks or not obs.get("trace_steps")
            or not c.get("scan_flops_per_step") or seconds is None):
        return None
    least = max(c["scan_flops_per_step"] / peaks["bf16_flops_per_s"],
                c["scan_bytes_per_step"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / obs["trace_steps"])

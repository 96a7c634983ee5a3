"""The compressed attention's scores and weighted values against the
chip's bf16 peak: the operations causal attention NEEDS forward and
backward for the held heads (the builder's count: half the sequence a
query on average, nothing masked out, what remat recomputes counts
nothing), over the device time under ``bigdl.cca.attend`` a step, over
the published peak.  Compute bounds it: a block of 1,024 queries reads
its keys once for 2 x 1,024 operations a key channel."""

from benchmarks import hlo_scopes


def read(obs):
    c = obs.get("cca_counts") or {}
    seconds = hlo_scopes.seconds_under(obs, "bigdl.cca.attend")
    if (not obs.get("peaks") or not obs.get("trace_steps")
            or not c.get("attend_flops_per_step") or seconds is None):
        return None
    seconds_per_step = seconds / obs["trace_steps"]
    return 100.0 * c["attend_flops_per_step"] / seconds_per_step \
        / obs["peaks"]["bf16_flops_per_s"]

"""Model FLOP/s utilization: the operations forward and backward need per
record (counted from the configuration's sizes by its builder) times the
records per second of the host part of the window, over chips times the
published bf16 peak.  An end-to-end utilization, not a roofline share."""

from benchmarks import lib


def read(obs):
    if not obs.get("peaks"):
        return None  # no published peak: not a chip run
    return lib.mfu_percent(obs["flops_per_record"],
                           obs["host_records_per_s"], obs["chips"],
                           obs["peaks"]["bf16_flops_per_s"])

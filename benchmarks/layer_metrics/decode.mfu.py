"""The whole serving loop's share of the chip's bf16 peak, in %: the
operations the prompt and answer tokens of the host part of the window
NEED (the builder's count, from the sizes) over that part of the window,
against ``bf16_flops_per_s``.  An end-to-end utilization: it bounds what
any one program's roofline can claim."""


def read(observed):
    flops, peaks = observed.get("host_needed_flops"), observed.get("peaks")
    seconds = observed.get("host_window_s")
    if not flops or not peaks or not seconds:
        return None
    return 100.0 * flops / (seconds * observed["chips"]
                            * peaks["bf16_flops_per_s"])

"""The decode step's share of its memory roofline, in %: the bytes a
step NEEDS (the builder's ``needed_bytes_per_step``: the parameters once
and the key/value positions in use, from the sizes and the lengths the
runner knows, never from what the implementation moves) over the step's
device time x the chip's ``hbm_bytes_per_s``.  Memory bounds a step of a
few slots: its operations would take under a tenth of that time."""


def read(observed):
    step = (observed.get("decode_programs") or {}).get("step")
    need, peaks = observed.get("needed_bytes_per_step"), observed.get("peaks")
    if not step or not step["count"] or not need or not peaks:
        return None
    return 100.0 * need / (step["median_s"] * peaks["hbm_bytes_per_s"])

"""Device time of one execution of the decode step's program, median
over the traced window (xplane, ``XLA Modules`` of device 0)."""


def read(observed):
    step = (observed.get("decode_programs") or {}).get("step")
    if not step or not step["count"]:
        return None
    return 1e3 * step["median_s"]

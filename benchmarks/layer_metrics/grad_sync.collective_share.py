"""Time in collective operations (all-reduce, all-gather, reduce-scatter,
collective-permute) on device 0 over the traced window.  TOTAL time, not
the exposed part: a collective that overlaps compute still counts."""


def read(obs):
    dev = obs.get("trace_device0") or {}
    if not dev.get("window_s") or "collective_s" not in dev:
        return None
    return dev["collective_s"] / dev["window_s"]

"""Share of the host part of the window that NO top-level span of the
training driver covers: 1 - (``stage_next`` + ``dispatch`` +
``device_wait`` + ``replay``) / host window (product telemetry, host
clock).  The four tile the driver's loop, a block each, so what is left
is host time nothing names.  Floored at 0: a span counts whole where it
starts, so one that straddles the window's end can carry the sum past
the window.  ``None`` where the program has no ``stage_next`` span."""

TOP_LEVEL = ("stage_next", "dispatch", "device_wait", "replay")


def read(obs):
    phases = obs.get("phase_seconds") or {}
    if "stage_next" not in phases:
        return None
    covered = sum(phases.get(c, 0.0) for c in TOP_LEVEL)
    return max(0.0, 1.0 - covered / obs["host_window_s"])

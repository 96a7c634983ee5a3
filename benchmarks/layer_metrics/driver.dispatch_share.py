"""Share of the host part of the window that the training driver spent
inside its ``dispatch`` spans (product telemetry, host clock)."""

from benchmarks import lib


def read(obs):
    return lib.phase_share(obs, "dispatch")

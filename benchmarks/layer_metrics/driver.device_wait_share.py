"""Share of the host part of the window that the training driver spent
waiting for the device in its loss fetch (``device_wait`` spans, product
telemetry, host clock)."""

from benchmarks import lib


def read(obs):
    return lib.phase_share(obs, "device_wait")

"""Share of the host part of the window that the stager spent stacking
mini-batches on the host and handing them to the device (``stage``
spans: ``host_stack`` + ``h2d_stage``, product telemetry, host clock)."""

from benchmarks import lib


def read(obs):
    return lib.phase_share(obs, "stage")

"""Share of the host part of the window that the stager spent copying
the pulled mini-batches into one block along the K axis (``block_stack``
spans inside ``host_stack``; product telemetry, host clock)."""

from benchmarks import lib


def read(obs):
    return lib.phase_share(obs, "block_stack")

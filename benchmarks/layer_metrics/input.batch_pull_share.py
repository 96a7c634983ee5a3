"""Share of the host part of the window that the driver's thread spent
pulling mini-batches from the host pipeline (``batch_pull`` spans inside
the stager's ``host_stack``; product telemetry, host clock): with
``MTSampleToMiniBatch`` the wait for the assembler's thread, with
``SampleToMiniBatch`` the assembly itself."""

from benchmarks import lib


def read(obs):
    return lib.phase_share(obs, "batch_pull")

"""Share of the host part of the window that the assembler's thread
spent stacking samples into mini-batches (``assemble`` spans, category
``batch_assemble``, which the stager records on a track of their own
from the stamps each ``MTSampleToMiniBatch`` batch carries; product
telemetry, host clock).  The work overlaps the driver's time: near 1 the
assembler sets the pace, and the driver's wait for it reads as
``input.batch_pull_share``.  An inline assembler (``SampleToMiniBatch``)
has no such span, and neither has a program from before PR 26."""

from benchmarks import lib


def read(obs):
    return lib.phase_share(obs, "batch_assemble")

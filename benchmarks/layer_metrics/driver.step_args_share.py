"""Share of the host part of the window that the training driver spent
making a block's step arguments (``step_args`` spans inside
``stage_next``: ``current_lr`` x k, ``fold_in`` x k, two ``asarray``s and
a ``stack``; product telemetry, host clock).  The device calls among
them are eager and queue behind the running block, so on a device-bound
cell this is waiting under another name."""

from benchmarks import lib


def read(obs):
    return lib.phase_share(obs, "step_args")

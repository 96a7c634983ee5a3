"""Self time of the instructions traced under ``bigdl.moe.route`` (the
router — for ZAYA an MLP in f32 at the highest precision on a state
that runs from layer to layer — the choice and the row plan) over
device busy time, device 0, traced window."""

from benchmarks import hlo_scopes


def read(obs):
    busy = (obs.get("trace_device0") or {}).get("busy_s")
    seconds = hlo_scopes.seconds_under(obs, "bigdl.moe.route")
    if not busy or seconds is None:
        return None
    return seconds / busy

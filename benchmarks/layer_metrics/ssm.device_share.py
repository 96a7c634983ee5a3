"""Self time of the instructions traced under ``bigdl.mamba.*`` (the
mixer's projections, conv, chunked scan and gated norm) over device
busy time, device 0, traced window."""

from benchmarks import hlo_scopes


def read(obs):
    busy = (obs.get("trace_device0") or {}).get("busy_s")
    seconds = hlo_scopes.seconds_under(obs, "bigdl.mamba.")
    if not busy or seconds is None:
        return None
    return seconds / busy

"""Self time of the instructions traced under ``bigdl.cca.mix`` (both
convolutions over the packed queries and keys, the query-key mean, the
unit length, the temperature and the rotary positions: all of it
bandwidth, no large product) over device busy time, device 0, traced
window."""

from benchmarks import hlo_scopes


def read(obs):
    busy = (obs.get("trace_device0") or {}).get("busy_s")
    seconds = hlo_scopes.seconds_under(obs, "bigdl.cca.mix")
    if not busy or seconds is None:
        return None
    return seconds / busy

"""Self time of the instructions traced under ``bigdl.cca.*`` (the
compressed attention's projections into and out of the latent, its
convolutions, mean, normalisation and rotary positions, the blocked
softmax) over device busy time, device 0, traced window."""

from benchmarks import hlo_scopes


def read(obs):
    busy = (obs.get("trace_device0") or {}).get("busy_s")
    seconds = hlo_scopes.seconds_under(obs, "bigdl.cca.")
    if not busy or seconds is None:
        return None
    return seconds / busy

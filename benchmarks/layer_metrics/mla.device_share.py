"""Self time of the instructions traced under ``bigdl.mla.*`` (the
latent-attention layer's projections, latent norm, rotary positions,
gate and causal attention) over device busy time, device 0, traced
window."""

from benchmarks import hlo_scopes


def read(obs):
    busy = (obs.get("trace_device0") or {}).get("busy_s")
    seconds = hlo_scopes.seconds_under(obs, "bigdl.mla.")
    if not busy or seconds is None:
        return None
    return seconds / busy

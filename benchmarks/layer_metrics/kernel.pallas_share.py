"""Time in Pallas custom calls over device busy time, device 0, traced
window.  The calls are found by the names the optimizer's own compiled
block gives its ``tpu_custom_call`` instructions."""


def read(obs):
    dev = obs.get("trace_device0") or {}
    names = ((obs.get("facts") or {}).get("op_names") or {}).get("pallas")
    if not dev.get("busy_s") or not names:
        return None
    return dev["pallas_s"] / dev["busy_s"]

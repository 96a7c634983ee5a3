"""Device busy time per training step: busy seconds of device 0 in the
traced window over the steps that ran in it — whole executions of the
optimizer's block (its seconds over the median length of one, since the
trace cuts the first and the last short) times the steps per block."""


def read(obs):
    dev = obs.get("trace_device0") or {}
    if not obs.get("trace_steps") or not dev.get("busy_s"):
        return None
    return 1e3 * dev["busy_s"] / obs["trace_steps"]

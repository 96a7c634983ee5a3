"""Share of the held experts' buffer rows that carried an assignment:
the model's own ``rows_held`` counter (summed over layers and over every
step of the run) over R x layers x steps.  1 minus it is the padding
paid for a step whose device work does not depend on the routing."""


def read(obs):
    c = obs.get("moe_counters") or {}
    if not c.get("rows"):
        return None
    return c["rows_held"] / c["rows"]

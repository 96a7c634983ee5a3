"""How uneven the held experts' load was: the largest total of one held
expert of one layer over the mean of all of them, from the model's own
``rows_by_expert`` counters over every step of the run.  1 is balance;
the static row buffer pays for whatever this reads in padding, never in
time."""


def read(obs):
    by_layer = (obs.get("moe_counters") or {}).get("rows_by_expert_by_layer")
    totals = [n for layer in by_layer or [] for n in layer]
    if not totals or not sum(totals):
        return None
    return max(totals) * len(totals) / sum(totals)

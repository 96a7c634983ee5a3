"""Assignments to a held expert that found no row in the buffer and read
0, the model's own counter summed over layers and over every step of the
run.  0 in a run that is ``correct``."""


def read(obs):
    c = obs.get("moe_counters") or {}
    if "rows_overflow" not in c:
        return None
    return float(c["rows_overflow"])

"""Bytes the collectives of one step put on the wire, per device, counted
from the shapes in the optimizer's own compiled block (a loop body counts
once, and at K=1 there is none).  A count: it repeats exactly."""


def read(obs):
    wire = (obs.get("facts") or {}).get("wire_bytes") or {}
    if not wire.get("total"):
        return None
    return wire["total"]

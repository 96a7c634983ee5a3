"""Median gap between consecutive tokens of one answer, over the host
part of the window (the runner's ``on_token`` stamps): the bare step as
a caller feels it, device time and the host's per-step work together."""


def read(observed):
    itl = observed.get("itl_ms")
    return itl["p50"] if itl else None

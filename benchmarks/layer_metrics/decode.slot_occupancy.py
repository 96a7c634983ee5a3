"""``active_slot_steps / (steps x slots)`` over the window, from the
service's own counters (``decode/active_slot_steps``, ``decode/steps``):
the share of the step batch that carried a sequence."""


def read(observed):
    c = observed.get("decode_counters")
    if not c or not c["steps"]:
        return None
    return c["active_slot_steps"] / (c["steps"] * c["slots"])

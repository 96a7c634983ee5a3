"""Key/value positions in use over positions reserved, summed over the
steps: the service's own ``decode/kv_positions_used`` (the active
slots' lengths a step) over ``decode/kv_positions_reserved`` (slots x
``max_seq_len`` a step).  ``None`` without ``observed["service"]`` or
before any step."""


def read(observed):
    svc = observed.get("service")
    if not svc or not svc.get("kv_positions_reserved"):
        return None
    return svc["kv_positions_used"] / svc["kv_positions_reserved"]

"""Device time of one admission: the prefill programs' and the splice
programs' seconds over the prefills traced (a mean: the buckets differ;
the execution the trace cuts at its edge counts with what it has)."""


def read(observed):
    programs = observed.get("decode_programs") or {}
    prefill = programs.get("prefill")
    if not prefill or not prefill["count"]:
        return None
    splice = programs.get("splice") or {"seconds": 0.0}
    return 1e3 * (prefill["seconds"] + splice["seconds"]) / prefill["count"]

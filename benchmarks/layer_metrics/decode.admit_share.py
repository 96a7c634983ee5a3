"""Share of the scheduler thread's working time that admissions take:
``decode_admit`` seconds over ``decode_schedule`` + ``decode_admit`` +
``decode_step`` (the top-level categories but ``decode_idle``; every
running sequence waits through an admission).  From the rows of
``stats()["decode"]["spans"]``, which run since the service started;
``None`` without ``observed["service"]`` or without a tracer in the
service."""

WORKING = ("decode_schedule", "decode_admit", "decode_step")


def read(observed):
    svc = observed.get("service")
    rows = svc.get("spans") if svc else None
    if not rows:
        return None
    seconds = dict.fromkeys(WORKING, 0.0)
    for row in rows.values():
        if row["cat"] in seconds:
            seconds[row["cat"]] += row["seconds"]
    working = sum(seconds.values())
    return seconds["decode_admit"] / working if working else None

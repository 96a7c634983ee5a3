"""Median ``dispatch`` span of the decode service: the call of the
step's executable alone, its transfers in and the wait for the device
apart (``stats()["decode"]["spans"]["dispatch"]["median_ms"]``, over
the newest 4,096 steps).  ``None`` without ``observed["service"]`` or
without a tracer in the service."""


def read(observed):
    svc = observed.get("service")
    row = svc.get("spans", {}).get("dispatch") if svc else None
    return row["median_ms"] if row else None

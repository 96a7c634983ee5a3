"""Bytes an admission copies from the device to read its first token:
``decode/first_fetch_bytes`` over ``decode/admissions`` (a mean over
the buckets that came).  ``None`` without ``observed["service"]`` or
before any admission."""


def read(observed):
    svc = observed.get("service")
    if not svc or not svc.get("admissions"):
        return None
    return svc["first_fetch_bytes"] / svc["admissions"]

"""95th percentile of the time from ``submit`` to admission, from the
service's ``decode/queue_wait_ms`` histogram
(``stats()["decode"]["queue_wait_ms"]``).  Above the knee it reads the
backlog.  ``None`` without ``observed["service"]`` or before any
admission."""


def read(observed):
    svc = observed.get("service")
    hist = svc.get("queue_wait_ms") if svc else None
    return hist.get("p95") if hist else None

"""What the host adds to a decode step: the median, over the newest
4,096 steps, of one ``step`` span less its ``device_wait``
(``stats()["decode"]["host_step_ms"]``: transfers in, the launch, the
fetch, argmax and callbacks).  ``None`` until the runner passes the
service's own ``stats()["decode"]`` on as ``observed["service"]``, and
for a service that held no tracer."""


def read(observed):
    svc = observed.get("service")
    return svc.get("host_step_ms") if svc else None

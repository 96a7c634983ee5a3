"""Share of the prefilled positions that were padding: 1 -
``decode/prefill_tokens`` / ``decode/prefill_tokens_padded`` (a
prompt's length against its bucket's, summed over admissions).
``None`` without ``observed["service"]`` or before any admission."""


def read(observed):
    svc = observed.get("service")
    if not svc or not svc.get("prefill_tokens_padded"):
        return None
    return 1.0 - svc["prefill_tokens"] / svc["prefill_tokens_padded"]

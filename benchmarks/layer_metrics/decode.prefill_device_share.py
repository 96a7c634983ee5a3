"""Share of the device's busy time under the prefill and splice
programs, over the traced window."""


def read(observed):
    programs = observed.get("decode_programs") or {}
    busy = observed.get("device_busy_s")
    if not busy or "step" not in programs:
        return None
    return sum(programs.get(k, {}).get("seconds", 0.0)
               for k in ("prefill", "splice")) / busy

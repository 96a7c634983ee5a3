#!/usr/bin/env python3
"""Drives ``benchmarks/run.py --tiny`` (which skips the look for a chip)
with the decode step BROKEN underneath, so that a test can see
``correct`` come out false:

    python3 benchmarks/tests/decode_faults.py <fault> <run.py's arguments>

``state_unchanged``: the step returns the key/value strips it was given;
``half_the_slots``: the lower half of the slot batch is left out (its
log-probabilities are the upper half's; a lightly loaded rehearsal fills
the low slots first); ``token_altered``: every
decoded token is the one beside the argmax; ``shifted_position``: the
step writes and reads one position too far; ``none``: nothing broken.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    import jax.numpy as jnp

    from bigdl_tpu.models import transformer as tm
    step = tm.transformer_lm_decode_step

    def broken(model, params, tokens, lengths, k, v):
        if fault == "shifted_position":
            return step(model, params, tokens, lengths + 1, k, v)
        lp, nk, nv = step(model, params, tokens, lengths, k, v)
        if fault == "state_unchanged":
            return lp, k, v
        if fault == "half_the_slots":
            half = lp.shape[0] // 2
            return jnp.concatenate([lp[half:], lp[half:]]), nk, nv
        if fault == "token_altered":
            return jnp.roll(lp, 1, axis=-1), nk, nv
        raise SystemExit(f"unknown fault {fault!r}")

    if fault != "none":
        tm.transformer_lm_decode_step = broken
    from benchmarks import run
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())

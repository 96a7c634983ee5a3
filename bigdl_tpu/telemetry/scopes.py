"""Named device scopes: the one helper through which a layer names the
device work it traces.

The tracer's spans (``tracer.py``) are host time; what a layer does on
the device is inside one compiled block, where no host span can see it.
``device_scope("moe.experts")`` is ``jax.named_scope("bigdl.moe.experts")``:
the name ends up in the ``op_name`` metadata of every HLO instruction
traced under it, forward and backward (``transpose(jvp(bigdl.moe.experts))``
still spells it), so a reader of the compiled module's text can lay
each instruction of a device trace under the innermost scope that
named it (``benchmarks/hlo_scopes.py``).  It costs nothing at run time:
the scope is metadata.
"""

from __future__ import annotations

import jax

SCOPE_PREFIX = "bigdl."


def device_scope(name: str):
    """Context manager naming the device work traced inside it
    ``bigdl.<name>`` (dotted, lower case: ``mamba.scan``)."""
    return jax.named_scope(SCOPE_PREFIX + name)

"""Compiled-vs-interpreted choice for the Pallas kernels.

A kernel must follow the platform its program is LOWERED for, not the
default device of the process that traces it: a proxy-attached pod
traces on its CPU backend and exports for the chip proxy's platform
(``isolation/client.py`` ``_trace_and_compile``), so a choice made from
``jax.devices()`` at trace time would ship the interpreted kernel to the
TPU. ``lax.platform_dependent`` defers the choice to lowering, where only
the branch of the target platform survives — the TPU program carries the
Mosaic kernel, the CPU program the interpreter running the identical
kernel body. There is no third branch: nothing drops to a reference.
"""

from __future__ import annotations

import jax


def kernel_call(make_call, *args, interpret: bool | None = None):
    """``make_call(interpret: bool)`` builds the ``pl.pallas_call``;
    ``interpret=None`` picks per lowering platform, a bool forces it."""
    if interpret is not None:
        return make_call(bool(interpret))(*args)
    return jax.lax.platform_dependent(*args, tpu=make_call(False),
                                      default=make_call(True))

"""The genuine ``jax.jit``, kept below both the attach shim and the
isolation runtime.

``attach.py`` replaces the public ``jax.jit`` with the tenant's shim and
hands the real one to :func:`keep`. The proxy's AOT compiles and the
client's tracing read it back through :func:`real_jit`, so neither
recurses into the shim, and ``isolation/`` never imports ``attach``.
"""

from __future__ import annotations

_kept = None


def keep(jit) -> None:
    """``attach`` installs its shim (``jit`` = the function it replaced)
    or removes it (``None``)."""
    global _kept
    _kept = jit


def real_jit():
    """The genuine ``jax.jit`` even while the attach shim has replaced the
    public attribute — framework internals (client tracing, the proxy's
    AOT compiles) must never recurse into the shim."""
    if _kept is not None:
        return _kept
    import jax

    return jax.jit

"""Force JAX onto N virtual CPU devices — the fake-multichip test backend.

``JAX_PLATFORMS=cpu`` holds a fresh process to the CPU backend; a process
that has already imported jax (but not yet used a backend) needs the
config API instead, and the host-platform device count requires
``XLA_FLAGS`` to be set before XLA parses it. Both tests/conftest.py and
the driver entry (``__graft_entry__.dryrun_multichip``) need all three, so
it lives here. This module must stay importable without jax side effects —
callers import it before jax initializes.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "xla_force_host_platform_device_count"


def set_host_device_count(n: int) -> None:
    """Rewrite ``XLA_FLAGS`` so the host platform exposes exactly *n*
    devices, replacing any preset (possibly wrong-count) flag."""
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(rf"--{_COUNT_FLAG}=\d+", "", flags).strip()
    os.environ["XLA_FLAGS"] = (flags + f" --{_COUNT_FLAG}={n}").strip()


def force_virtual_cpu(n: int) -> bool:
    """Best-effort: make ``jax.devices("cpu")`` return ≥ *n* devices.

    Sets the env vars, then the platform list through the config API (for
    a process that imported jax before this call). Returns True when the running process now exposes ≥ *n*
    CPU devices; False when it cannot (jax backend already initialized with
    a different flag set — the caller must fall back to a fresh process).
    Does NOT raise on failure: probing device count necessarily initializes
    the backend, and callers need the boolean to decide on the fallback.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    set_host_device_count(n)

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass  # already initialized; the probe below decides
    try:
        return len(jax.devices("cpu")) >= n
    except RuntimeError:
        return False

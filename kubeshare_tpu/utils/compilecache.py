"""One persistent XLA compile cache for every process that compiles.

The chip proxy (it compiles every tenant's program), the model CLIs, the
gang runner and ``chip_smoke.py``'s children all call
:func:`enable_compile_cache` before their first compile. The cache's path
is part of its key, so it must never move: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
set in code; where it is not, the cache is ``<checkout>/.jax_cache``
(git-ignored) — never a temp name, pid or timestamp.
"""

from __future__ import annotations

import atexit
import os
import sys
from pathlib import Path

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")

#: cacheable compile requests / served from the cache / compiled and
#: written to it — counted from JAX's own monitoring events
_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
           "/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "written"}
counts = {"requests": 0, "hits": 0, "written": 0}
_enabled = ""


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key:
        counts[key] += 1


def _report() -> None:
    # the process's real stderr: at exit a logger's stream may be closed
    print(f"compile cache {_enabled}: requests={counts['requests']} "
          f"hits={counts['hits']} written={counts['written']}",
          file=sys.__stderr__, flush=True)


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one fixed place and
    count its traffic (logged once at exit). Returns the directory.
    Idempotent; call before the process's first compile."""
    global _enabled
    if _enabled:
        return _enabled
    import jax

    path = os.environ.get(ENV_CACHE_DIR, "")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.monitoring.register_event_listener(_on_event)
    atexit.register(_report)
    _enabled = path
    return path

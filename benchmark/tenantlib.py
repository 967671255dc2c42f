"""What the benchmark's tenant programs share: plain JAX, and no model's
name. The model is the configuration's ``binding`` (``models/<family>.py``),
its sizes are its ``counts``. A tenant calls ``jax.jit`` and knows nothing
of the proxy; whatever its environment attached (the ``_shim`` on
PYTHONPATH) decides where a jitted call runs.

Import this only inside a tenant process (it imports jax).
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

import readlib

ADAM_B1 = 0.9


def load_spec(argv) -> dict:
    if len(argv) != 2:
        raise SystemExit("usage: <tenant>.py <spec.json>")
    return json.loads(Path(argv[1]).read_text())


def model(config: dict, longest: int):
    """``(binding, sizes)`` of the configuration: the module that builds
    its model on the program's code, and ``{"vocab", "positions"}`` as
    run. ``longest`` is the longest sequence this tenant will send."""
    sizes = readlib.sizes(config)
    if longest > sizes["positions"]:
        raise SystemExit(f"a sequence of {longest} exceeds the "
                         f"configuration's {sizes['positions']} positions")
    return readlib.named(config, "binding"), sizes


def leaf_names(tree) -> list[str]:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path) for path, _ in paths]


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def say(tag: str, payload: dict) -> None:
    """One line the parent waits for: ``TAG {json}`` on stdout."""
    print(f"{tag} {json.dumps(payload)}", flush=True)


def wait_go(rundir: str, timeout_s: float = 900.0) -> dict:
    """The barrier: block until the parent writes ``go.json`` (the window's
    two ends on CLOCK_MONOTONIC, shared by every process of the machine)."""
    path = Path(rundir) / "go.json"
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if path.exists():
            try:
                return json.loads(path.read_text())
            except json.JSONDecodeError:
                pass        # mid-write; the parent renames, but be safe
        if os.getppid() == 1:
            raise SystemExit("parent gone before the window opened")
        time.sleep(0.01)
    raise SystemExit("no go.json: the window never opened")


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05) if left > 0.002 else 0)

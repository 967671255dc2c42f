"""What the benchmark's tenant programs share: plain JAX on the program's
own model code. A tenant calls ``jax.jit`` and knows nothing of the proxy;
whatever its environment attached (the ``_shim`` on PYTHONPATH) decides
where a jitted call runs.

Import this only inside a tenant process (it imports jax).
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from kubeshare_tpu.models import transformer as T
from kubeshare_tpu.ops.flash_attention import flash_attention

ADAM_B1 = 0.9


def load_spec(argv) -> dict:
    if len(argv) != 2:
        raise SystemExit("usage: <tenant>.py <spec.json>")
    return json.loads(Path(argv[1]).read_text())


def model_dims(config: dict) -> dict:
    """Sizes from the configuration's file. The head count is a module
    constant of the program today (PERF.md lists the argument for R1): set
    it before ``init``."""
    T.HEADS = int(config["n_head"])
    return {"seq_len": int(config["n_positions"]),
            "vocab": int(config["vocab_size"]),
            "dim": int(config["n_embd"]), "layers": int(config["n_layer"])}


def bench_attn(q, k, v):
    """The program's flash kernels under a stable scope, so the trace
    reduction finds attention whatever later implements it."""
    with jax.named_scope("bench_attn"):
        return flash_attention(q, k, v, causal=True)


def init_on_device(dims: dict, key_words: np.ndarray):
    """Weights made on the device from the seed, in one jitted call."""
    return jax.jit(lambda key: T.init(key, **dims))(
        np.asarray(key_words, np.uint32))


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

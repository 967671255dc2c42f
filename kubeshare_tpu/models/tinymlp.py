"""Microsecond-step MLP — the serving plane's model.

Not a workload parity item: a 32-wide two-layer MLP on batch 8 steps in
tens of microseconds on CPU, so ``serving/batcher.py``'s ``ProxyServable``
and ``scripts/bench_serving.py`` measure the sharing path around a
program, not the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..ops import dense_apply, dense_init, softmax_cross_entropy
from .common import main_cli

BATCH_SIZE = 8
FEATURES = 32
CLASSES = 4


def init(key) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "fc1": dense_init(k1, FEATURES, FEATURES),
        "fc2": dense_init(k2, FEATURES, CLASSES),
    }


def apply(params: dict, x: jax.Array) -> jax.Array:
    x = jax.nn.relu(dense_apply(params["fc1"], x))
    return dense_apply(params["fc2"], x)


def loss_fn(params: dict, batch) -> jax.Array:
    x, y = batch
    return softmax_cross_entropy(apply(params, x), y)


def batch_fn(key):
    kx, ky = jax.random.split(key)
    x = jax.random.normal(kx, (BATCH_SIZE, FEATURES), jnp.float32)
    y = jax.random.randint(ky, (BATCH_SIZE,), 0, CLASSES)
    return x, y


if __name__ == "__main__":
    main_cli("tinymlp", init, loss_fn, batch_fn)

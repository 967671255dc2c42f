"""Layer primitives as (init, apply) pairs over plain pytrees.

Design notes (TPU-first):

- Every apply is shape-static and jit-safe; recurrences use ``lax.scan``.
- Matmuls/convs accept a ``dtype`` so models can run activations in
  bfloat16 (MXU-native) while keeping fp32 parameters.
- NHWC conv layout — XLA:TPU's preferred layout for small models.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def _uniform(key, shape, scale, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -scale, scale)


# --- dense -------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int) -> dict:
    wkey, bkey = jax.random.split(key)
    scale = math.sqrt(1.0 / in_dim)
    return {"w": _uniform(wkey, (in_dim, out_dim), scale),
            "b": _uniform(bkey, (out_dim,), scale)}


def dense_apply(params: dict, x: jax.Array, dtype=None) -> jax.Array:
    w, b = params["w"], params["b"]
    if dtype is not None:
        x, w, b = x.astype(dtype), w.astype(dtype), b.astype(dtype)
    return x @ w + b


# --- conv2d (NHWC) -----------------------------------------------------------

def conv2d_init(key, in_ch: int, out_ch: int, kernel: int = 3) -> dict:
    wkey, bkey = jax.random.split(key)
    fan_in = in_ch * kernel * kernel
    scale = math.sqrt(2.0 / fan_in)  # He init
    return {"w": jax.random.normal(wkey, (kernel, kernel, in_ch, out_ch)) * scale,
            "b": jnp.zeros((out_ch,))}


def conv2d_apply(params: dict, x: jax.Array, stride: int = 1,
                 padding: str = "SAME", dtype=None) -> jax.Array:
    w, b = params["w"], params["b"]
    if dtype is not None:
        x, w, b = x.astype(dtype), w.astype(dtype), b.astype(dtype)
    y = lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def max_pool(x: jax.Array, window: int = 2, stride: int | None = None) -> jax.Array:
    stride = stride or window
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1), (1, stride, stride, 1), "VALID")


def avg_pool(x: jax.Array, window: int = 2, stride: int | None = None) -> jax.Array:
    stride = stride or window
    summed = lax.reduce_window(
        x, 0.0, lax.add, (1, window, window, 1), (1, stride, stride, 1), "VALID")
    return summed / (window * window)


# --- layernorm ---------------------------------------------------------------

def layernorm_init(dim: int) -> dict:
    return {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))}


def layernorm_apply(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Normalize the trailing axis in fp32 (bf16 variance loses too many
    bits), then cast back to the input dtype."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


# --- batchnorm (training-mode batch statistics) ------------------------------

def batchnorm_init(ch: int) -> dict:
    return {"scale": jnp.ones((ch,)), "bias": jnp.zeros((ch,))}


def batchnorm_apply(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axes, keepdims=True)
    var = jnp.var(x, axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    return y * params["scale"] + params["bias"]


# --- LSTM --------------------------------------------------------------------

def lstm_init(key, in_dim: int, hidden: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    scale = math.sqrt(1.0 / hidden)
    return {
        "wi": _uniform(k1, (in_dim, 4 * hidden), scale),
        "wh": _uniform(k2, (hidden, 4 * hidden), scale),
        "b": _uniform(k3, (4 * hidden,), scale),
    }


def lstm_apply(params: dict, xs: jax.Array, dtype=None) -> jax.Array:
    """Run an LSTM over ``xs`` of shape [batch, time, in_dim] via
    ``lax.scan`` (jit-safe recurrence); returns hidden states
    [batch, time, hidden]."""
    wi, wh, b = params["wi"], params["wh"], params["b"]
    if dtype is not None:
        xs, wi, wh, b = (a.astype(dtype) for a in (xs, wi, wh, b))
    hidden = wh.shape[0]
    batch = xs.shape[0]
    h0 = jnp.zeros((batch, hidden), xs.dtype)
    c0 = jnp.zeros((batch, hidden), xs.dtype)

    def step(carry, x_t):
        h, c = carry
        gates = x_t @ wi + h @ wh + b
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    (_, _), hs = lax.scan(step, (h0, c0), jnp.swapaxes(xs, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


# --- RMSNorm and the gated MLP (the block of today's open decoders) ----------

def rmsnorm_init(dim: int) -> dict:
    return {"scale": jnp.ones((dim,))}


def rmsnorm_apply(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * scale`` over the trailing axis, in
    fp32 whatever the input's dtype, cast back to it."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * params["scale"]).astype(x.dtype)


def gated_mlp_init(key, dim: int, hidden: int) -> dict:
    """``w1`` gates, ``w3`` carries, ``w2`` projects back; no bias."""
    k1, k3, k2 = jax.random.split(key, 3)
    s_in, s_hid = math.sqrt(1.0 / dim), math.sqrt(1.0 / hidden)
    return {"w1": _uniform(k1, (dim, hidden), s_in),
            "w3": _uniform(k3, (dim, hidden), s_in),
            "w2": _uniform(k2, (hidden, dim), s_hid)}


def gated_mlp_apply(params: dict, x: jax.Array, dtype=None) -> jax.Array:
    """``(silu(x w1) * (x w3)) w2``: the gate is fp32 between the matmuls
    (XLA fuses it into their epilogue), the operands ``dtype``."""
    w1, w3, w2 = params["w1"], params["w3"], params["w2"]
    if dtype is not None:
        x, w1, w3, w2 = (a.astype(dtype) for a in (x, w1, w3, w2))
    gate = jax.nn.silu((x @ w1).astype(jnp.float32))
    return (gate * (x @ w3).astype(jnp.float32)).astype(x.dtype) @ w2

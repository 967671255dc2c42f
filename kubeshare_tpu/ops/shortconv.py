"""Gated short convolution: the token mixer that replaces attention in
most layers of the LFM2 family.

    [B, C, X] = split3(u W_in);  z = B * X
    c_t = sum_j k_j * z_{t-(L-1)+j}   (depthwise, causal, z_t = 0 for t < 0)
    out = (C * c) W_out

With a kernel of L = 3 taps the convolution is three shifted
multiply-adds over (batch, seq, dim) that XLA fuses with the two gates
into one pass between the projections: no kernel of its own unless a
trace asks for one. The gates and the taps are fp32 between the matmuls,
the matmul operands ``dtype``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def short_conv_init(key, dim: int, kernel: int = 3) -> dict:
    ki, kc, ko = jax.random.split(key, 3)
    s = math.sqrt(1.0 / dim)
    return {"in": jax.random.uniform(ki, (dim, 3 * dim), jnp.float32, -s, s),
            "conv": jax.random.uniform(kc, (kernel, dim), jnp.float32,
                                       -math.sqrt(1.0 / kernel),
                                       math.sqrt(1.0 / kernel)),
            "out": jax.random.uniform(ko, (dim, dim), jnp.float32, -s, s)}


def causal_depthwise_conv(z: jax.Array, taps: jax.Array) -> jax.Array:
    """``z``: (batch, seq, dim); ``taps``: (L, dim). Output position ``t``
    sees ``z[t-L+1 .. t]`` and nothing later: tap ``j`` multiplies ``z``
    shifted right by ``L-1-j`` with zeros shifted in."""
    kernel, seq = taps.shape[0], z.shape[1]
    out = z * taps[kernel - 1]
    for j in range(kernel - 1):
        shift = kernel - 1 - j
        shifted = jnp.pad(z, ((0, 0), (shift, 0), (0, 0)))[:, :seq]
        out = out + shifted * taps[j]
    return out


def short_conv_apply(params: dict, x: jax.Array, dtype=None) -> jax.Array:
    w_in, taps, w_out = params["in"], params["conv"], params["out"]
    if dtype is not None:
        x, w_in, w_out = (a.astype(dtype) for a in (x, w_in, w_out))
    b, c, xx = jnp.split((x @ w_in).astype(jnp.float32), 3, axis=-1)
    y = c * causal_depthwise_conv(b * xx, taps.astype(jnp.float32))
    return y.astype(x.dtype) @ w_out

"""Minimal parameterized NN primitives for the codec.

Plain pytrees of arrays + pure functions (no flax/haiku dependency): params are
nested dicts, apply functions are jit/pjit-friendly and shard_map-safe.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "init_conv",
    "conv2d",
    "init_conv_transpose",
    "conv2d_transpose",
]

_DN = ("NHWC", "HWIO", "NHWC")


def init_conv(key, kh, kw, cin, cout, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(kh * kw * cin)
    wk, bk = jax.random.split(key)
    return {
        "w": jax.random.uniform(wk, (kh, kw, cin, cout), dtype, -scale, scale),
        "b": jnp.zeros((cout,), dtype),
    }


def conv2d(params, x, stride=1, padding="SAME", feature_group_count=1):
    s = (stride, stride) if isinstance(stride, int) else stride
    y = jax.lax.conv_general_dilated(
        x,
        params["w"].astype(x.dtype),
        window_strides=s,
        padding=padding,
        dimension_numbers=_DN,
        feature_group_count=feature_group_count,
    )
    return y + params["b"].astype(x.dtype)


def init_conv_transpose(key, kh, kw, cin, cout, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(kh * kw * cin)
    wk, bk = jax.random.split(key)
    return {
        "w": jax.random.uniform(wk, (kh, kw, cin, cout), dtype, -scale, scale),
        "b": jnp.zeros((cout,), dtype),
    }


def conv2d_transpose(params, x, stride=2, padding="SAME"):
    s = (stride, stride) if isinstance(stride, int) else stride
    y = jax.lax.conv_transpose(
        x,
        params["w"].astype(x.dtype),
        strides=s,
        padding=padding,
        dimension_numbers=_DN,
    )
    return y + params["b"].astype(x.dtype)

"""The precision of the reference's weight products, shared by every family.

A family's ``logits`` takes ``dot`` and runs every weight product through it:
``f32_dot`` for the float32 reference, at ``Precision.HIGHEST``, or one of
``CONTROLS``: ``int8_dot`` and ``fp8_dot``, the same reference with every
weight product in int8 or in float8 e4m3 (weights scaled per output column,
activations per row), the steps below the bfloat16 the configurations compute
in.  It imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def f32_dot(a, w):
    """a (..., K) @ w (K, N) in float32."""
    return jnp.einsum("...k,kn->...n", a, w, precision=HI)


def _int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).astype(jnp.int8), scale


def int8_dot(a, w):
    """a (..., K) @ w (K, N) with both operands in int8."""
    qa, sa = _int8(a, -1)
    qw, sw = _int8(w, 0)
    acc = jax.lax.dot_general(qa, qw, (((a.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa * sw


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale


def fp8_dot(a, w):
    """a (..., K) @ w (K, N) with both operands in float8 e4m3."""
    qa, sa = _fp8(a, -1)
    qw, sw = _fp8(w, 0)
    return f32_dot(qa, qw) * sa * sw


CONTROLS = {"int8": int8_dot, "fp8": fp8_dot}

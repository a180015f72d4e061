"""Plain float32 reference of the served dense decoder, and its controls.

It follows the equations of the repository's dense decoder (RMSNorm, rotary
position over the whole head, causal softmax attention with grouped KV heads,
a GLU or plain two-matrix MLP, untied unembedding) in float32 with every
matrix product at ``Precision.HIGHEST``, one sequence and one layer at a time.
It imports nothing of the program: it reads the weights that ``weights.make``
makes and the sizes of the configuration file.  Where those equations depart
from the published model, the configuration file's ``departures`` say so.

The weights arrive in the program's layout: query heads may be padded (the
layer's ``wq`` has ``H >= num_attention_heads`` heads).  With ``g`` KV heads
the padded heads form ``g`` equal blocks; in each block the first
``num_attention_heads / g`` heads are real and attend to that block's KV head.

``int8_dot`` and ``fp8_dot`` are the controls: the same reference with every
weight product in int8 or in float8 e4m3 (weights scaled per output column,
activations per row), the steps below the bfloat16 the configuration
computes in.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
ROPE_THETA = 10_000.0


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


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def _rope(x, pos):
    """x (T, H, D), pos (T,)."""
    d = x.shape[-1]
    freqs = 1.0 / (ROPE_THETA ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


ACTS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0))),
}


def head_layout(padded: int, n_heads: int, n_kv: int):
    """(real query head indices in the padded layout, KV head of each)."""
    if padded % n_kv or n_heads % n_kv:
        raise ValueError(f"no GQA layout for {padded} padded heads, "
                         f"{n_heads} heads, {n_kv} KV heads")
    block, real = padded // n_kv, n_heads // n_kv
    heads = [h for h in range(padded) if h % block < real]
    return np.asarray(heads), np.asarray([h // block for h in heads])


@partial(jax.jit, static_argnames=("cfg", "dot"))
def _layer(x, lp, heads, kv_of, *, cfg, dot):
    T, d = x.shape
    pos = jnp.arange(T)
    wq = lp["attn"]["wq"]["w"][:, heads]  # (d, h, D)
    D = wq.shape[-1]
    h = _rms(x, lp["ln1"]["g"])
    q = dot(h, wq.reshape(d, -1)).reshape(T, -1, D)
    k = dot(h, lp["attn"]["wk"]["w"].reshape(d, -1)).reshape(T, -1, D)
    v = dot(h, lp["attn"]["wv"]["w"].reshape(d, -1)).reshape(T, -1, D)
    q, k = _rope(q, pos), _rope(k, pos)
    k, v = k[:, kv_of], v[:, kv_of]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(D)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    wo = lp["attn"]["wo"]["w"][heads]  # (h, D, d)
    x = x + dot(o.reshape(T, -1), wo.reshape(-1, d))
    h = _rms(x, lp["ln2"]["g"])
    mlp, act = lp["mlp"], ACTS[cfg[0]]
    u = act(dot(h, mlp["wi"]["w"]))
    if "wu" in mlp:
        u = u * dot(h, mlp["wu"]["w"])
    return x + dot(u, mlp["wd"]["w"])


@partial(jax.jit, static_argnames=("first", "vocab", "dot"))
def _head(x, g, table, *, first, vocab, dot):
    return dot(_rms(x[first:], g), table[:vocab].T)


def logits(params, tokens, cfg: dict, first: int, dot=f32_dot) -> jax.Array:
    """Logits (T - first, vocab) of ``tokens`` (T,) at positions first..T-1.

    ``cfg`` is the configuration file; ``params`` the tree of
    ``weights.make``.  The layers run one at a time."""
    layers = params["layers"]
    n_layers = layers["ln1"]["g"].shape[0]
    heads, kv_of = head_layout(layers["attn"]["wq"]["w"].shape[2],
                               cfg["num_attention_heads"],
                               cfg["num_key_value_heads"])
    heads, kv_of = jnp.asarray(heads), jnp.asarray(kv_of)
    x = jnp.take(params["embed"]["table"], jnp.asarray(tokens), axis=0)
    static = (cfg["run"]["act"],)
    for i in range(n_layers):
        lp = jax.tree.map(lambda t: t[i], layers)
        x = _layer(x, lp, heads, kv_of, cfg=static, dot=dot)
    return _head(x, params["final_norm"]["g"], params["unembed"]["table"],
                 first=first, vocab=cfg["vocab_size"], dot=dot)

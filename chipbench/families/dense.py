"""The dense decoder: the family of every configuration file that says
``"family": "dense"`` (``bench.family`` lists what a family holds).

``logits`` is the plain float32 reference.  It follows the equations of the
repository's dense decoder (RMSNorm, rotary position over the whole head,
causal softmax attention with grouped KV heads, a GLU or plain two-matrix MLP,
untied unembedding), one sequence and one layer at a time, with every weight
product through ``dot`` (``precision.py``).  It imports nothing of the
program: it reads the weights that ``weights.make`` makes and the sizes of the
configuration file.  Where those equations depart from the published model,
the configuration file's ``departures`` say so.

The weights arrive in the program's layout: query heads may be padded (the
layer's ``wq`` has ``H >= num_attention_heads`` heads).  With ``g`` KV heads
the padded heads form ``g`` equal blocks; in each block the first
``num_attention_heads / g`` heads are real and attend to that block's KV head.

The counts follow ``benchmarks/cost_model.py`` (matmul = 2*m*n*k, causal
attention = 4 * keys * heads * head_dim per query and layer), but count only
useful work: the configuration's logical heads, not padded ones; a request's
own tokens, not the pad of its prefill row; attention over the keys a token
really sees; and one unembedding per served token.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from precision import HI, f32_dot

EPS = 1e-6
ROPE_THETA = 10_000.0
COUNTERS = ()


# ------------------------------ the program ----------------------------------


def program_config(config: dict):
    """The program's configuration for a configuration file."""
    from repro.models import get_config

    cfg = get_config(config["arch"])
    run = config["run"]
    if (cfg.act, cfg.mlp_kind) != (run["act"], run["mlp"]):
        raise ValueError(f"{config['arch']}: the program runs {cfg.act}/"
                         f"{cfg.mlp_kind}, the file says {run['act']}/"
                         f"{run['mlp']}")
    return cfg.replace(
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        d_ff=config["intermediate_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], vocab=config["vocab_size"],
        param_dtype=run["param_dtype"], compute_dtype=run["compute_dtype"],
        approx=dataclasses.replace(cfg.approx, mode=run["approx_mode"],
                                   e_a=run["e_a"]))


def scale(names: tuple, shape: tuple):
    """N(0, 1) times this is the leaf at ``names`` (its path of keys) of
    ``shape``: 1/sqrt(fan_in) for projections, 0.02 for the embeddings and
    the attention output, ``None`` for norm gains (ones).  Leaves under
    ``layers`` are stacked over the layer axis."""
    if names[-1] == "g":
        return None
    if "table" in names or "wo" in names:
        return 0.02
    fan_in = shape[1] if names[0] == "layers" else shape[0]
    return fan_in ** -0.5


# ------------------------------ the reference --------------------------------


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


def logits(params, tokens, config: dict, first: int, dot=f32_dot) -> jax.Array:
    """Logits (T - first, vocab) of ``tokens`` (T,) at positions first..T-1.

    ``config`` is the configuration file; ``params`` the tree of
    ``weights.make``.  The layers run one at a time."""
    layers = params["layers"]
    n_layers = layers["ln1"]["g"].shape[0]
    heads, kv_of = head_layout(layers["attn"]["wq"]["w"].shape[2],
                               config["num_attention_heads"],
                               config["num_key_value_heads"])
    heads, kv_of = jnp.asarray(heads), jnp.asarray(kv_of)
    x = jnp.take(params["embed"]["table"], jnp.asarray(tokens), axis=0)
    static = (config["run"]["act"],)
    for i in range(n_layers):
        lp = jax.tree.map(lambda t: t[i], layers)
        x = _layer(x, lp, heads, kv_of, cfg=static, dot=dot)
    return _head(x, params["final_norm"]["g"], params["unembed"]["table"],
                 first=first, vocab=config["vocab_size"], dot=dot)


# ------------------------------ useful work ----------------------------------


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def layer_params(config: dict) -> int:
    """Weights of one layer's matrix products, at the logical head counts."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    D = head_dim(config)
    h, g = config["num_attention_heads"], config["num_key_value_heads"]
    attn = d * h * D + 2 * d * g * D + h * D * d
    mlp = (3 if config["run"]["mlp"] == "glu" else 2) * d * ff
    return attn + mlp


def request_flops(config: dict, prompt_len: int, n_out: int) -> float:
    """Useful FLOPs to serve one request greedily: the forward pass over its
    prompt and over every served token but the last (which is never fed
    back), and the unembedding of each served token."""
    L, d = config["num_hidden_layers"], config["hidden_size"]
    V = config["vocab_size"]
    width = config["num_attention_heads"] * head_dim(config)
    positions = prompt_len + n_out - 1
    keys = positions * (positions + 1) // 2  # token i attends to i + 1 keys
    return (2.0 * layer_params(config) * L * positions
            + 4.0 * width * L * keys
            + 2.0 * d * V * n_out)


def act_elements(config: dict, counters: dict) -> int:
    """Elements the MLP activation is applied to in a window whose engine
    counters are ``counters``: ``prefills`` rounds of (batch, prefill_len)
    rows and ``batch_steps - prefills`` decode ticks of batch rows, each
    round counting the rows that ran."""
    c = counters
    ticks = c["batch_steps"] - c["prefills"]
    return ((c["prefills"] * c["batch"] * c["prefill_len"]
             + ticks * c["batch"])
            * config["intermediate_size"] * config["num_hidden_layers"])

"""Useful work of a served request, and the activation kernel's elements.

The formulas follow ``benchmarks/cost_model.py`` (matmul = 2*m*n*k, causal
attention = 4 * keys * heads * head_dim per query and layer), but count only
useful work: the configuration's logical heads, not padded ones; a request's
own tokens, not the pad of its prefill row; attention over the keys a token
really sees; and one unembedding per served token.  ``cfg`` is a
configuration file as a dict.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_params(cfg: dict) -> int:
    """Weights of one layer's matrix products, at the logical head counts."""
    d, ff, D = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * h * D + 2 * d * g * D + h * D * d
    mlp = (3 if cfg["run"]["mlp"] == "glu" else 2) * d * ff
    return attn + mlp


def request_flops(cfg: dict, prompt_len: int, n_out: int) -> float:
    """Useful FLOPs to serve one request greedily: the forward pass over its
    prompt and over every served token but the last (which is never fed
    back), and the unembedding of each served token."""
    L, d, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    width = cfg["num_attention_heads"] * head_dim(cfg)
    positions = prompt_len + n_out - 1
    keys = positions * (positions + 1) // 2  # token i attends to i + 1 keys
    return (2.0 * layer_params(cfg) * L * positions
            + 4.0 * width * L * keys
            + 2.0 * d * V * n_out)


def act_elements(cfg: dict, prefills: int, ticks: int, batch: int,
                 prefill_len: int) -> int:
    """Elements the MLP activation is applied to by ``prefills`` prefill calls
    of (batch, prefill_len) rows and ``ticks`` decode ticks of batch rows."""
    return ((prefills * batch * prefill_len + ticks * batch)
            * cfg["intermediate_size"] * cfg["num_hidden_layers"])


# The activation reads and writes each element once, in the model's bfloat16.
ACT_BYTES_PER_ELEMENT = 2 + 2

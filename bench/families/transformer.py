"""Dense decoder transformer (codeqwen1.5-7b): pre-norm blocks of
grouped-query attention with optional QKV bias and rotary embedding, then a
SwiGLU MLP, every projection on CIM tiles, one chip a layer.

What the benchmark knows of this family: the program's sizes for a
configuration, the seeded weights, the reference's layers, the packed CIM
calls of one engine step and the model FLOPs.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import reference
from bench.reference import rms
from bench.weights import normal


def arch_kw(c: dict) -> dict:
    """Keyword overrides of the registry's ArchConfig."""
    return dict(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                tie_embeddings=c["tie_word_embeddings"],
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
                qkv_bias=c["qkv_bias"], rope_theta=c["rope_theta"])


def weights(key, c: dict) -> Dict:
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    f = c["intermediate_size"]
    ks = iter(jax.random.split(key, 32))
    proj = lambda *sh: normal(next(ks), (L,) + sh, 1.0 / math.sqrt(sh[0]))
    scale = lambda n: 1.0 + normal(next(ks), (L, n), 0.05)
    layers = {
        "wq": proj(d, nh * hd), "wk": proj(d, nkv * hd),
        "wv": proj(d, nkv * hd), "wo": proj(nh * hd, d),
        "w_g": proj(d, f), "w_i": proj(d, f), "w_o": proj(f, d),
        "ln1": scale(d), "ln2": scale(d),
    }
    if c["qkv_bias"]:
        layers["bq"] = normal(next(ks), (L, nh * hd), 0.02)
        layers["bk"] = normal(next(ks), (L, nkv * hd), 0.02)
        layers["bv"] = normal(next(ks), (L, nkv * hd), 0.02)
    return {"embed": normal(next(ks), (V, d), 0.02),
            "unembed": normal(next(ks), (d, V), 0.02),
            "ln_f": 1.0 + normal(next(ks), (d,), 0.05),
            "layers": layers}


def _layer(c, p, chip, x, precision):
    """x (B, S, d) padded sequences."""
    b, s, d = x.shape
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps = c["rms_norm_eps"]
    flat = lambda a: a.reshape(b * s, -1)
    h = flat(rms(x, p["ln1"], eps))
    q, k, v = chip("wq", h), chip("wk", h), chip("wv", h)
    if c["qkv_bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)
    items = reference.scalars(c)
    attn = jnp.stack([reference.attention(q[i], k[i], v[i], c_items=items,
                                          precision=precision)
                      for i in range(b)])
    x = x + chip("wo", attn.reshape(b * s, nh * hd)).reshape(b, s, d)
    h2 = flat(rms(x, p["ln2"], eps))
    g = jax.nn.silu(chip("w_g", h2)) * chip("w_i", h2)
    return x + chip("w_o", g).reshape(b, s, d)


def layers(c: dict, params: Dict, x, deploy_key, precision: str):
    return reference.each_layer(c, params, x, deploy_key, precision, _layer)


def _projections(c: dict) -> List[Tuple[int, int]]:
    """(K, N) of each CIM projection of one layer."""
    d, f = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d)]


def dispatches(c: dict) -> List[Tuple[int, int]]:
    """(K, N) of every packed CIM call of one engine step: wq, wk, wv, wo,
    w_g, w_i, w_o of each layer."""
    return _projections(c) * c["num_hidden_layers"]


def _attention_flops(c: dict) -> float:
    """Scores and weighted sum, per layer and position of KV length."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"]


def row_flops(c: dict, kv_len: int, head: bool) -> float:
    """Model FLOPs of one useful token row at KV length kv_len (the row
    itself included), with or without the LM head."""
    per_layer = 2.0 * sum(k * n for k, n in _projections(c))
    per_layer += _attention_flops(c) * kv_len
    flops = per_layer * c["num_hidden_layers"]
    if head:
        flops += 2.0 * c["hidden_size"] * c["vocab_size"]
    return flops


def request_flops(c: dict, prompt_len: int, n_tokens: int) -> float:
    """Model FLOPs a request needs: every prompt row (the LM head only on
    the last, which yields the first token; attention over KV lengths
    1..P in closed form), then one decode row per further token."""
    att = _attention_flops(c) * c["num_hidden_layers"]
    total = prompt_len * row_flops(c, 0, False) \
        + att * prompt_len * (prompt_len + 1) / 2
    total += 2.0 * c["hidden_size"] * c["vocab_size"]
    for j in range(1, n_tokens):
        total += row_flops(c, prompt_len + j, True)
    return total

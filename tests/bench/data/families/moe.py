"""Fine-grained mixture of experts (the program's deepseek-moe-16b): pre-norm
blocks of multi-head attention, then a router over every routed expert,
the top-k of them and the shared experts, all projections on CIM tiles.
A family file for the benchmark's tests, whose tree and packed calls are
not a dense transformer's: it shows that a new family needs no edit under
bench/.

The program's semantics (models/moe.moe_ffn, nn.deploy_transformer_cim):
  * router in float32, softmax over the top-k logits (no renormalisation
    over all experts); dropless dispatch, so every routed token reaches
    its expert;
  * one chip per (layer, expert) for ew_g, ew_i, ew_o, deployed from
    fold_in(deploy_key, 7919 + e); the shared experts' sw_g, sw_i, sw_o
    on the layer's own chip with the attention projections.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import reference
from bench.reference import Chip, contract, rms
from bench.weights import normal

LAYER_CHIP = ("wq", "wk", "wv", "wo", "sw_g", "sw_i", "sw_o")
EXPERT_CHIP = ("ew_g", "ew_i", "ew_o")
EXPERT_KEY = 7919


def arch_kw(c: dict) -> dict:
    return dict(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                tie_embeddings=c["tie_word_embeddings"],
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
                qkv_bias=c["attention_bias"], rope_theta=c["rope_theta"],
                n_experts=c["n_routed_experts"],
                top_k=c["num_experts_per_tok"],
                n_shared_experts=c["n_shared_experts"],
                d_expert=c["moe_intermediate_size"])


def weights(key, c: dict) -> Dict:
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    e, de = c["n_routed_experts"], c["moe_intermediate_size"]
    ds = de * c["n_shared_experts"]
    ks = iter(jax.random.split(key, 32))
    proj = lambda *sh: normal(next(ks), (L,) + sh,
                              1.0 / math.sqrt(sh[-2]))
    scale = lambda n: 1.0 + normal(next(ks), (L, n), 0.05)
    layers = {
        "wq": proj(d, q), "wk": proj(d, kv), "wv": proj(d, kv),
        "wo": proj(q, d), "ln1": scale(d), "ln2": scale(d),
        "router": proj(d, e),
        "ew_g": proj(e, d, de), "ew_i": proj(e, d, de),
        "ew_o": proj(e, de, d),
        "sw_g": proj(d, ds), "sw_i": proj(d, ds), "sw_o": proj(ds, d),
    }
    return {"embed": normal(next(ks), (V, d), 0.02),
            "unembed": normal(next(ks), (d, V), 0.02),
            "ln_f": 1.0 + normal(next(ks), (d,), 0.05),
            "layers": layers}


def _attention(c, p, chip, x, precision):
    b, s, d = x.shape
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    h = rms(x, p["ln1"], c["rms_norm_eps"]).reshape(b * s, d)
    q = chip("wq", h).reshape(b, s, nh, hd)
    k = chip("wk", h).reshape(b, s, nkv, hd)
    v = chip("wv", h).reshape(b, s, nkv, hd)
    items = reference.scalars(c)
    attn = jnp.stack([reference.attention(q[i], k[i], v[i], c_items=items,
                                          precision=precision)
                      for i in range(b)])
    return x + chip("wo", attn.reshape(b * s, nh * hd)).reshape(b, s, d)


def _moe(c, p, chip, experts, x, precision):
    b, s, d = x.shape
    t = rms(x, p["ln2"], c["rms_norm_eps"]).reshape(b * s, d)
    logits = contract("td,de->te", t, p["router"], precision)
    gate, idx = jax.lax.top_k(logits, c["num_experts_per_tok"])
    gate = jax.nn.softmax(gate, axis=-1)
    y = chip("sw_o", jax.nn.silu(chip("sw_g", t)) * chip("sw_i", t))
    for e, ex in enumerate(experts):
        w = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)
        y = y + w[:, None] * ex("ew_o", jax.nn.silu(ex("ew_g", t))
                                * ex("ew_i", t))
    return x + y.reshape(b, s, d)


def layers(c: dict, params: Dict, x, deploy_key, precision: str):
    for li in range(c["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
        chip = Chip(c, p, reference.chip_key(deploy_key, li), precision,
                    names=LAYER_CHIP)
        experts = [Chip(c, {n: p[n][e] for n in EXPERT_CHIP},
                        jax.random.fold_in(jax.random.fold_in(
                            deploy_key, EXPERT_KEY + e), li),
                        precision, names=EXPERT_CHIP)
                   for e in range(c["n_routed_experts"])]
        x = _moe(c, p, chip, experts, _attention(c, p, chip, x, precision),
                 precision)
    return x


def _shapes(c: dict):
    d = c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    de = c["moe_intermediate_size"]
    ds = de * c["n_shared_experts"]
    attn = [(d, q), (d, kv), (d, kv), (q, d)]
    return attn, [(d, de), (d, de), (de, d)], [(d, ds), (d, ds), (ds, d)]


def dispatches(c: dict) -> List[Tuple[int, int]]:
    """Per layer: wq, wk, wv, wo; ew_g, ew_i, ew_o of each routed expert
    (one dispatch each, dropless: every expert runs on every row); the
    shared experts' sw_g, sw_i, sw_o."""
    attn, expert, shared = _shapes(c)
    per_layer = attn + expert * c["n_routed_experts"] + shared
    return per_layer * c["num_hidden_layers"]


def row_flops(c: dict, kv_len: int, head: bool) -> float:
    """Model FLOPs of one useful row: attention projections, router, the
    top-k routed and the shared experts, attention over kv_len."""
    attn, expert, shared = _shapes(c)
    params = sum(k * n for k, n in attn + shared) \
        + c["num_experts_per_tok"] * sum(k * n for k, n in expert) \
        + c["hidden_size"] * c["n_routed_experts"]
    per_layer = 2.0 * params \
        + 4.0 * c["num_attention_heads"] * c["head_dim"] * kv_len
    flops = per_layer * c["num_hidden_layers"]
    if head:
        flops += 2.0 * c["hidden_size"] * c["vocab_size"]
    return flops


def request_flops(c: dict, prompt_len: int, n_tokens: int) -> float:
    total = sum(row_flops(c, kv, False) for kv in range(1, prompt_len + 1))
    total += 2.0 * c["hidden_size"] * c["vocab_size"]
    for j in range(1, n_tokens):
        total += row_flops(c, prompt_len + j, True)
    return total

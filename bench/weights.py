"""Seeded weights for a configuration file, made on the device in one jitted
call, in float32 (the type the packed CIM path serves), laid out as the
program's parameter tree.

The benchmark makes the weights, not the program: the plain reference
(bench/reference.py) regenerates the same arrays from the same seed and so
takes nothing that the system under test made.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _transformer(key, c: dict) -> Dict:
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    f = c["intermediate_size"]
    ks = iter(jax.random.split(key, 32))
    proj = lambda *sh: _normal(next(ks), (L,) + sh, 1.0 / math.sqrt(sh[0]))
    scale = lambda n: 1.0 + _normal(next(ks), (L, n), 0.05)
    layers = {
        "wq": proj(d, nh * hd), "wk": proj(d, nkv * hd),
        "wv": proj(d, nkv * hd), "wo": proj(nh * hd, d),
        "w_g": proj(d, f), "w_i": proj(d, f), "w_o": proj(f, d),
        "ln1": scale(d), "ln2": scale(d),
    }
    if c["qkv_bias"]:
        layers["bq"] = _normal(next(ks), (L, nh * hd), 0.02)
        layers["bk"] = _normal(next(ks), (L, nkv * hd), 0.02)
        layers["bv"] = _normal(next(ks), (L, nkv * hd), 0.02)
    return {"embed": _normal(next(ks), (V, d), 0.02),
            "unembed": _normal(next(ks), (d, V), 0.02),
            "ln_f": 1.0 + _normal(next(ks), (d,), 0.05),
            "layers": layers}


def _rwkv6(key, c: dict) -> Dict:
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    f, n, r = c["intermediate_size"], c["head_size"], c["decay_lora_rank"]
    h = c["attention_hidden_size"] // n
    ks = iter(jax.random.split(key, 32))
    proj = lambda *sh: _normal(next(ks), (L,) + sh, 1.0 / math.sqrt(sh[0]))
    unif = lambda shape, lo, hi: jax.random.uniform(
        next(ks), (L,) + shape, jnp.float32, lo, hi)
    layers = {
        "ln1": 1.0 + _normal(next(ks), (L, d), 0.05),
        "ln2": 1.0 + _normal(next(ks), (L, d), 0.05),
        "wr": proj(d, d), "wk": proj(d, d), "wv": proj(d, d),
        "wg": proj(d, d), "wo": proj(d, d),
        "w_base": unif((d,), -1.0, 0.0),
        "w_lora_a": proj(d, r), "w_lora_b": proj(r, d),
        "mu": unif((5, d), 0.0, 1.0),
        "u": _normal(next(ks), (L, h, n), 0.5),
        "ck": proj(d, f), "cv": proj(f, d), "cr": proj(d, d),
        "cmu": unif((2, d), 0.0, 1.0),
    }
    return {"embed": _normal(next(ks), (V, d), 0.02),
            "unembed": _normal(next(ks), (d, V), 0.02),
            "ln_f": 1.0 + _normal(next(ks), (d,), 0.05),
            "layers": layers}


_FAMILIES = {"transformer": _transformer, "rwkv6": _rwkv6}


def make(config: dict, key) -> Dict:
    """All weights of `config` from `key`, in one jitted call."""
    fam = _FAMILIES[config["family"]]
    return jax.jit(functools.partial(fam, c=config))(key)

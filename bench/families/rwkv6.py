"""RWKV-6 (Finch): attention-free blocks of a time mix, whose per-head
recurrence carries a matrix state with data-dependent decay, and a channel
mix, every projection on CIM tiles, one chip a layer. The configuration's
`departures` list where the program differs from the paper.

What the benchmark knows of this family: the program's sizes for a
configuration, the seeded weights, the reference's layers, the packed CIM
calls of one engine step and the model FLOPs.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import reference
from bench.reference import contract, rms
from bench.weights import normal


def arch_kw(c: dict) -> dict:
    """Keyword overrides of the registry's ArchConfig."""
    h = c["attention_hidden_size"] // c["head_size"]
    return dict(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                tie_embeddings=c["tie_word_embeddings"],
                n_heads=h, n_kv_heads=h)


def weights(key, c: dict) -> Dict:
    L, d, V = c["num_hidden_layers"], c["hidden_size"], c["vocab_size"]
    f, n, r = c["intermediate_size"], c["head_size"], c["decay_lora_rank"]
    h = c["attention_hidden_size"] // n
    ks = iter(jax.random.split(key, 32))
    proj = lambda *sh: normal(next(ks), (L,) + sh, 1.0 / math.sqrt(sh[0]))
    unif = lambda shape, lo, hi: jax.random.uniform(
        next(ks), (L,) + shape, jnp.float32, lo, hi)
    layers = {
        "ln1": 1.0 + normal(next(ks), (L, d), 0.05),
        "ln2": 1.0 + normal(next(ks), (L, d), 0.05),
        "wr": proj(d, d), "wk": proj(d, d), "wv": proj(d, d),
        "wg": proj(d, d), "wo": proj(d, d),
        "w_base": unif((d,), -1.0, 0.0),
        "w_lora_a": proj(d, r), "w_lora_b": proj(r, d),
        "mu": unif((5, d), 0.0, 1.0),
        "u": normal(next(ks), (L, h, n), 0.5),
        "ck": proj(d, f), "cv": proj(f, d), "cr": proj(d, d),
        "cmu": unif((2, d), 0.0, 1.0),
    }
    return {"embed": normal(next(ks), (V, d), 0.02),
            "unembed": normal(next(ks), (d, V), 0.02),
            "ln_f": 1.0 + normal(next(ks), (d,), 0.05),
            "layers": layers}


@functools.partial(jax.jit, static_argnames=("precision",))
def _wkv(r, k, v, w, u, *, precision):
    """RWKV-6 recurrence, one sequence, as a sequential scan over tokens:
    o_t = r_t (S + diag(u) k_t v_t^T), S <- diag(w_t) S + k_t v_t^T."""
    def step(S, inp):
        rt, kt, vt, wt = inp                                # (H, N)
        kv = kt[:, :, None] * vt[:, None, :]
        o = contract("hn,hnm->hm", rt, S + u[:, :, None] * kv, precision)
        return S * wt[:, :, None] + kv, o

    h, n = r.shape[1], r.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((h, n, n), jnp.float32),
                        (r, k, v, w))
    return o


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _layer(c, p, chip, x, precision):
    b, s, d = x.shape
    n = c["head_size"]
    h = c["attention_hidden_size"] // n
    eps = c["rms_norm_eps"]
    flat = lambda a: a.reshape(b * s, -1)
    xn = rms(x, p["ln1"], eps)
    xs = _shift(xn)
    mix = lambda i: flat(xn + (xs - xn) * p["mu"][i])
    r, k, v = chip("wr", mix(0)), chip("wk", mix(1)), chip("wv", mix(2))
    lora = contract("sr,rd->sd", jnp.tanh(contract(
        "sd,dr->sr", mix(3), p["w_lora_a"], precision)), p["w_lora_b"],
        precision)
    w = jnp.exp(-jnp.exp(p["w_base"] + lora))
    g = jax.nn.silu(chip("wg", mix(4)))
    hv = lambda a: a.reshape(b, s, h, n)
    o = jnp.stack([_wkv(hv(r)[i], hv(k)[i], hv(v)[i], hv(w)[i], p["u"],
                        precision=precision) for i in range(b)])
    x = x + chip("wo", o.reshape(b * s, d) * g).reshape(b, s, d)
    xn2 = rms(x, p["ln2"], eps)
    xs2 = _shift(xn2)
    xk = flat(xn2 + (xs2 - xn2) * p["cmu"][0])
    xr = flat(xn2 + (xs2 - xn2) * p["cmu"][1])
    kk = jnp.square(jax.nn.relu(chip("ck", xk)))
    return x + (jax.nn.sigmoid(chip("cr", xr)) * chip("cv", kk)
                ).reshape(b, s, d)


def layers(c: dict, params: Dict, x, deploy_key, precision: str):
    return reference.each_layer(c, params, x, deploy_key, precision, _layer)


def _projections(c: dict) -> List[Tuple[int, int]]:
    """(K, N) of each CIM projection of one layer."""
    d, f = c["hidden_size"], c["intermediate_size"]
    return [(d, d), (d, d), (d, d), (d, d), (d, d), (d, f), (f, d), (d, d)]


def dispatches(c: dict) -> List[Tuple[int, int]]:
    """(K, N) of every packed CIM call of one engine step: wr, wk, wv, wg,
    wo, ck, cv, cr of each layer."""
    return _projections(c) * c["num_hidden_layers"]


def row_flops(c: dict, kv_len: int, head: bool) -> float:
    """Model FLOPs of one useful token row (the recurrence's cost does not
    grow with position: kv_len is unused), with or without the LM head."""
    d = c["hidden_size"]
    per_layer = 2.0 * sum(k * n for k, n in _projections(c))
    per_layer += 2.0 * 2 * d * c["decay_lora_rank"] + 4.0 * d * c["head_size"]
    flops = per_layer * c["num_hidden_layers"]
    if head:
        flops += 2.0 * d * c["vocab_size"]
    return flops


def request_flops(c: dict, prompt_len: int, n_tokens: int) -> float:
    """Model FLOPs a request needs: every prompt row (the LM head only on
    the last, which yields the first token), then one decode row per
    further token."""
    total = prompt_len * row_flops(c, 0, False)
    total += 2.0 * c["hidden_size"] * c["vocab_size"]
    for j in range(1, n_tokens):
        total += row_flops(c, prompt_len + j, True)
    return total

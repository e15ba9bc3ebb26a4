"""RWKV-6 "Finch" (attention-free, data-dependent decay) — rwkv6-7b.

Per head (size N=64): state S in R^{NxN};
    w_t = exp(-exp(w_base + lora_w(x_t)))            (data-dependent decay)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)        (u = per-head bonus)
plus token-shift interpolation on the inputs of r/k/v/w/g projections and a
gated (g) output. Channel-mix is the usual squared-relu K/V mix with token
shift. Training uses a time-chunked scan (chunk the sequence, carry S between
chunks) — the chunk matmuls hit the MXU instead of a length-T elementwise
scan; decode carries S directly (O(1) state — why this arch runs long_500k).

NeuRRAM note: the recurrent S update is the TNSA's BL->BL recurrent-MVM mode.
With cfg.cim_mode == "packed" the time-mix/channel-mix projections serve
from per-layer compiled CIM chips (models/nn.deploy_recurrent_cim) in both
the chunked prefill and the O(1) decode path; the S update stays digital
float (state-dependent — nothing weight-stationary to program).
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

HEAD = 64          # rwkv6 head size
LORA = 32          # decay lora rank


def layer_params(key, cfg, dtype) -> Dict:
    d = cfg.d_model
    h = d // HEAD
    ks = iter(jax.random.split(key, 16))
    s = lambda *sh: (jax.random.normal(next(ks), sh) /
                     math.sqrt(sh[0])).astype(dtype)
    p = {
        "ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
        # time-mix projections
        "wr": s(d, d), "wk": s(d, d), "wv": s(d, d), "wg": s(d, d),
        "wo": s(d, d),
        # data-dependent decay lora
        "w_base": jnp.zeros((d,), dtype),
        "w_lora_a": s(d, LORA), "w_lora_b": s(LORA, d),
        # token-shift mix coefficients for r/k/v/w/g
        "mu": (0.5 * jnp.ones((5, d))).astype(dtype),
        "u": jnp.zeros((h, HEAD), dtype),          # per-head bonus
        # channel mix
        "ck": s(d, cfg.d_ff), "cv": s(cfg.d_ff, d), "cr": s(d, d),
        "cmu": (0.5 * jnp.ones((2, d))).astype(dtype),
    }
    return p


def _token_shift(x, x_prev):
    """(B,T,d): shift sequence right by one; x_prev fills t=0."""
    return jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)


def _time_mix_chunk(p, x, x_last, S0, cfg, chunk: int = 32):
    """Chunked linear-attention evaluation of the RWKV-6 recurrence.

    x: (B,T,d). S0: (B,H,N,N) carry. Returns (y, S_T, x_T).

    The r/k/v/g/out projections route through `cim_linear` (via
    routed_linear), so with cim_mode == "packed" each one executes as a
    packed Pallas dispatch on this layer's compiled chip
    (nn.deploy_recurrent_cim). The decay lora (rank-32) and the S update
    itself stay digital float — nothing weight-stationary to program."""
    from .transformer import routed_linear
    b, t, d = x.shape
    h = d // HEAD
    xs = _token_shift(x, x_last)
    mix = lambda i: x + (xs - x) * p["mu"][i]
    r = routed_linear(mix(0), p, "wr", cfg, seed=1).reshape(b, t, h, HEAD)
    k = routed_linear(mix(1), p, "wk", cfg, seed=2).reshape(b, t, h, HEAD)
    v = routed_linear(mix(2), p, "wv", cfg, seed=3).reshape(b, t, h, HEAD)
    wdec = p["w_base"] + jnp.tanh(mix(3) @ p["w_lora_a"]) @ p["w_lora_b"]
    w = jnp.exp(-jnp.exp(wdec.astype(jnp.float32))).reshape(b, t, h, HEAD)
    g = jax.nn.silu(routed_linear(mix(4), p, "wg", cfg, seed=4))

    # pad time to a chunk multiple; padded steps: w=1 (no decay), k=v=0
    chunk = min(chunk, t)
    t_pad = -t % chunk
    if t_pad:
        r = jnp.pad(r, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, t_pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    t_eff = t + t_pad

    nchunk = t_eff // chunk
    rc = r.reshape(b, nchunk, chunk, h, HEAD)
    kc = k.reshape(b, nchunk, chunk, h, HEAD)
    vc = v.reshape(b, nchunk, chunk, h, HEAD)
    wc = w.reshape(b, nchunk, chunk, h, HEAD)

    def chunk_step(S, inp):
        rč, kč, vč, wč = inp                    # (B, C, H, N)
        rč = rč.astype(jnp.float32)
        kč = kč.astype(jnp.float32)
        vč = vč.astype(jnp.float32)
        # cumulative log-decay inside the chunk; all exponentials below are of
        # CLIPPED NON-POSITIVE quantities (numerically stable for any w)
        logw = jnp.log(wč + 1e-38)
        cum = jnp.cumsum(logw, axis=1)          # inclusive (B,C,H,N)
        cum_excl = cum - logw
        dec_in = jnp.exp(cum_excl)              # decay from chunk start to t-1
        dec_all = jnp.exp(cum[:, -1:])          # full-chunk decay
        # contribution of carried state: r_t . (prod_{<t} w) S
        r_eff = rč * dec_in
        y_state = jnp.einsum("bchn,bhnm->bchm", r_eff, S)
        # intra-chunk (causal, strictly lower; diagonal handled by the bonus):
        # factor for (s -> t, s<t) is exp(cumexcl_t - cumincl_s) <= 1
        dpair = jnp.exp(jnp.clip(cum_excl[:, :, None] - cum[:, None, :],
                                 -60.0, 0.0))   # (B,C,C,H,N)
        cidx = jnp.arange(rč.shape[1])
        causal = (cidx[:, None] > cidx[None, :])[None, :, :, None, None]
        att = jnp.einsum("bchn,bdhn,bcdhn->bhcd", rč, kč, dpair * causal)
        y_intra = jnp.einsum("bhcd,bdhn->bchn", att, vč)
        # bonus (current token): r_t . diag(u) k_t v_t
        bonus = jnp.einsum("bchn,hn,bchn->bch", rč,
                           p["u"].astype(jnp.float32), kč)
        y_bonus = bonus[..., None] * vč
        # state update to end of chunk: k_s decays by exp(cum_last - cum_s)
        k_carry = kč * jnp.exp(jnp.clip(cum[:, -1:] - cum,
                                        -60.0, 0.0))
        S_new = S * dec_all[:, 0, :, :, None] \
            + jnp.einsum("bchn,bchm->bhnm", k_carry, vč)
        return S_new, y_state + y_intra + y_bonus

    inp = (jnp.swapaxes(rc, 0, 1), jnp.swapaxes(kc, 0, 1),
           jnp.swapaxes(vc, 0, 1), jnp.swapaxes(wc, 0, 1))
    S_T, ys = jax.lax.scan(chunk_step, S0.astype(jnp.float32), inp)
    y = jnp.swapaxes(ys, 0, 1).reshape(b, t_eff, d)[:, :t].astype(x.dtype)
    return routed_linear(y * g, p, "wo", cfg, seed=5), S_T, x[:, -1]


def _channel_mix(p, x, x_last, cfg):
    from .transformer import routed_linear
    xs = _token_shift(x, x_last)
    xk = x + (xs - x) * p["cmu"][0]
    xr = x + (xs - x) * p["cmu"][1]
    kk = jnp.square(jax.nn.relu(routed_linear(xk, p, "ck", cfg, seed=6)))
    return jax.nn.sigmoid(routed_linear(xr, p, "cr", cfg, seed=7)) \
        * routed_linear(kk, p, "cv", cfg, seed=8)


def forward(layers_p, x, cfg):
    """Training/prefill forward over all layers (scan, remat)."""
    b, t, d = x.shape
    h = d // HEAD

    from .transformer import _remat_policy
    @functools.partial(jax.checkpoint, policy=_remat_policy(cfg))
    def body(x, p):
        from .transformer import rms_norm, constrain_batch
        x = constrain_batch(x, cfg)
        S0 = jnp.zeros((b, h, HEAD, HEAD), jnp.float32)
        x_last = jnp.zeros((b, d), x.dtype)
        y, _, _ = _time_mix_chunk(p, rms_norm(x, p["ln1"]), x_last, S0, cfg)
        x = x + y
        x = x + _channel_mix(p, rms_norm(x, p["ln2"]),
                             jnp.zeros((b, d), x.dtype), cfg)
        return x, None

    from .transformer import scan_layers
    x, _ = scan_layers(body, x, layers_p,
                       unroll=cfg.n_layers if cfg.scan_unroll else 1)
    return x


# ------------------------------------------------------------- decode path

def init_state(cfg, batch, dtype):
    d = cfg.d_model
    h = d // HEAD
    return {
        "S": jnp.zeros((cfg.n_layers, batch, h, HEAD, HEAD), jnp.float32),
        "x_tm": jnp.zeros((cfg.n_layers, batch, d), dtype),   # time-mix shift
        "x_cm": jnp.zeros((cfg.n_layers, batch, d), dtype),   # channel shift
        "len": jnp.zeros((), jnp.int32),
    }


def prefill(params, state, tokens, cfg):
    """Chunked prefill: process a whole prompt, carrying per-layer state.
    Returns (last-position logits, filled state)."""
    from .transformer import rms_norm, _softcap, constrain_batch, scan_layers
    x = params["embed"][tokens].astype(cfg.dtype)            # (B, T, d)
    b, t, d = x.shape
    h = d // HEAD

    def body(x, inp):
        p, S0, x_tm, x_cm = inp
        x = constrain_batch(x, cfg)
        xn = rms_norm(x, p["ln1"])
        y, S_T, x_tm_new = _time_mix_chunk(p, xn, x_tm, S0, cfg)
        x = x + y
        xn2 = rms_norm(x, p["ln2"])
        y2 = _channel_mix(p, xn2, x_cm, cfg)
        x = x + y2
        return x, (S_T, x_tm_new, xn2[:, -1])

    x, (S_new, x_tm_new, x_cm_new) = scan_layers(
        body, x, (params["layers"], state["S"], state["x_tm"],
                  state["x_cm"]),
        unroll=cfg.n_layers if cfg.scan_unroll else 1)
    x = rms_norm(x[:, -1], params["ln_f"])
    unemb = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = _softcap((x @ unemb).astype(jnp.float32), cfg.final_softcap)
    new_state = {"S": S_new, "x_tm": x_tm_new, "x_cm": x_cm_new,
                 "len": state["len"] + t}
    return logits, new_state


def decode_step(params, state, tokens, cfg):
    """O(1)-state decode: tokens (B,1) -> (logits, new state). Projections
    route through `cim_linear` like the chunked prefill path, so packed CIM
    serving covers decode with the SAME per-layer chips (one dispatch per
    projection per step)."""
    from .transformer import rms_norm, _softcap, routed_linear, scan_layers
    x = params["embed"][tokens[:, 0]].astype(cfg.dtype)      # (B, d)
    b, d = x.shape
    h = d // HEAD

    def body(x, inp):
        p, S, x_tm, x_cm = inp
        xn = rms_norm(x, p["ln1"])
        mix = lambda i: xn + (x_tm - xn) * p["mu"][i]
        r = routed_linear(mix(0), p, "wr", cfg, seed=1).reshape(b, h, HEAD)
        k = routed_linear(mix(1), p, "wk", cfg, seed=2).reshape(b, h, HEAD)
        v = routed_linear(mix(2), p, "wv", cfg, seed=3).reshape(b, h, HEAD)
        wdec = p["w_base"] + jnp.tanh(mix(3) @ p["w_lora_a"]) @ p["w_lora_b"]
        w = jnp.exp(-jnp.exp(wdec.astype(jnp.float32))).reshape(b, h, HEAD)
        g = jax.nn.silu(routed_linear(mix(4), p, "wg", cfg, seed=4))
        kv = jnp.einsum("bhn,bhm->bhnm", k, v)
        out = jnp.einsum("bhn,bhnm->bhm", r,
                         S + p["u"].astype(jnp.float32)[None, :, :, None] * kv)
        S_new = S * w[..., None] + kv
        y = routed_linear(out.reshape(b, d).astype(x.dtype) * g, p, "wo",
                          cfg, seed=5)
        x = x + y
        xn2 = rms_norm(x, p["ln2"])
        xk = xn2 + (x_cm - xn2) * p["cmu"][0]
        xr = xn2 + (x_cm - xn2) * p["cmu"][1]
        kk = jnp.square(jax.nn.relu(routed_linear(xk, p, "ck", cfg, seed=6)))
        x = x + jax.nn.sigmoid(routed_linear(xr, p, "cr", cfg, seed=7)) \
            * routed_linear(kk, p, "cv", cfg, seed=8)
        return x, (S_new, xn, xn2)

    x, (S_new, x_tm_new, x_cm_new) = scan_layers(
        body, x, (params["layers"], state["S"], state["x_tm"], state["x_cm"]),
        unroll=cfg.n_layers if cfg.scan_unroll else 1)
    x = rms_norm(x, params["ln_f"])
    unemb = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = _softcap((x @ unemb).astype(jnp.float32), cfg.final_softcap)
    new_state = {"S": S_new, "x_tm": x_tm_new, "x_cm": x_cm_new,
                 "len": state["len"] + 1}
    return logits, new_state

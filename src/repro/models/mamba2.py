"""Mamba-2 (SSD) blocks + shared-attention hybrid — zamba2-7b.

Mamba-2 head recurrence (state N=ssm_state, head dim P=ssm_head):
    h_t = exp(a dt_t) h_{t-1} + dt_t * (B_t outer x_t)     h in R^{NxP}
    y_t = C_t^T h_t + D * x_t
with per-head scalar decay a<0, input-dependent dt (softplus), B/C shared
across heads within a group (single group here). Training uses a chunked scan
(SSD block decomposition) so chunk matmuls hit the MXU.

Zamba2 hybrid: a stack of Mamba-2 blocks with ONE shared full-attention +
MLP block (single weight copy) invoked every `hybrid_attn_every` layers —
weight sharing as in the Zamba papers. Decode state is O(1) per layer (the
reason this arch runs the long_500k cell).

With cfg.cim_mode == "packed" the in/out projections and the hybrid MLP
serve from per-layer compiled CIM chips, and the shared attention block's
dense projections from their own chip (models/nn.deploy_recurrent_cim);
the h recurrence stays digital float.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from ..core.mapping import join_tile_stacks, split_tile_stacks


def layer_params(key, cfg, dtype) -> Dict:
    d = cfg.d_model
    d_in = 2 * d                      # expand factor 2
    n_heads = d_in // cfg.ssm_head
    ks = iter(jax.random.split(key, 10))
    s = lambda *sh: (jax.random.normal(next(ks), sh) /
                     math.sqrt(sh[0])).astype(dtype)
    return {
        "ln": jnp.ones((d,), dtype),
        "in_proj": s(d, 2 * d_in + 2 * cfg.ssm_state + n_heads),
        "out_proj": s(d_in, d),
        "a_log": jnp.zeros((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "dd": jnp.ones((n_heads,), dtype),     # skip connection D
        "ln2": jnp.ones((d,), dtype),
        "w_g": s(d, cfg.d_ff), "w_i": s(d, cfg.d_ff), "w_o": s(cfg.d_ff, d),
    }


def _ssd_chunk(p, x, cfg, chunk: int = 64, h0=None):
    """x: (B,T,d) normalized input -> ((B,T,d) mixer output, final state).
    h0: optional (B,H,N,P) carried state (prefill).

    in_proj/out_proj route through `cim_linear` (via routed_linear): with
    cim_mode == "packed" each executes as a packed Pallas dispatch on this
    layer's compiled chip (nn.deploy_recurrent_cim). The h recurrence stays
    digital float — state-dependent, nothing weight-stationary."""
    from .transformer import routed_linear
    b, t, d = x.shape
    d_in = 2 * d
    n = cfg.ssm_state
    nh = d_in // cfg.ssm_head
    ph = cfg.ssm_head

    zxbcdt = routed_linear(x, p, "in_proj", cfg, seed=11)
    z, xin, bmat, cmat, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + n, 2 * d_in + 2 * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,T,H)
    a = -jnp.exp(p["a_log"])                                     # (H,)
    xh = xin.reshape(b, t, nh, ph)
    decay = jnp.exp(a[None, None] * dt)                          # (B,T,H)

    # pad time to a chunk multiple; padded steps are identity (decay=1, dt=0)
    chunk = min(chunk, t)
    t_pad = -t % chunk
    if t_pad:
        xh = jnp.pad(xh, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, t_pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, t_pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, t_pad), (0, 0)))
        decay = jnp.pad(decay, ((0, 0), (0, t_pad), (0, 0)),
                        constant_values=1.0)
    t_eff = t + t_pad

    nchunk = t_eff // chunk
    xh_c = xh.reshape(b, nchunk, chunk, nh, ph)
    b_c = bmat.reshape(b, nchunk, chunk, n)
    c_c = cmat.reshape(b, nchunk, chunk, n)
    dt_c = dt.reshape(b, nchunk, chunk, nh)
    dec_c = decay.reshape(b, nchunk, chunk, nh)
    xh = xh[:, :t]

    def chunk_step(h0, inp):
        xč, bč, cč, dtč, decč = inp          # (B,C,...)
        logd = jnp.log(decč + 1e-38)
        cum = jnp.cumsum(logd, axis=1)        # (B,C,H) inclusive
        # h_t includes decay at t, so the h0 factor at step t is inclusive
        dec_from_start = jnp.exp(cum)
        # carried-state contribution: y = C_t^T (decay h0)
        y_state = jnp.einsum("bcn,bhnp,bch->bchp", cč, h0, dec_from_start)
        # intra-chunk: y_t = sum_{s<=t} C_t.B_s dt_s decay(s..t) x_s
        att = jnp.einsum("bcn,bdn->bcd", cč, bč)            # (B,C,C)
        ci = jnp.arange(cč.shape[1])
        causal = ci[:, None] >= ci[None, :]
        # decay(s->t) per head = exp(cum_t - cum_s)
        ddec = jnp.exp(jnp.clip(cum[:, :, None, :] - cum[:, None, :, :],
                                -60.0, 0.0))                # (B,C,C,H)
        w = att[..., None] * ddec * causal[None, :, :, None]
        y_intra = jnp.einsum("bcdh,bdh,bdhp->bchp", w, dtč, xč)
        # state update: carry decays by the full chunk, inputs by (s..end)
        dec_to_end = jnp.exp(cum[:, -1:, :] - cum)          # (B,C,H)
        h_new = h0 * jnp.exp(cum[:, -1])[..., None, None]   # (B,H,N,P)
        h_upd = jnp.einsum("bcn,bch,bch,bchp->bhnp", bč, dtč, dec_to_end, xč)
        return h_new + h_upd, y_state + y_intra

    if h0 is None:
        h0 = jnp.zeros((b, nh, n, ph), jnp.float32)
    inp = tuple(jnp.swapaxes(a_, 0, 1) for a_ in
                (xh_c, b_c, c_c, dt_c, dec_c))
    h_T, ys = jax.lax.scan(chunk_step, h0, inp)
    y = jnp.swapaxes(ys, 0, 1).reshape(b, t_eff, nh, ph)[:, :t]
    y = y + p["dd"][None, None, :, None].astype(jnp.float32) \
        * xh.astype(jnp.float32)
    y = y.reshape(b, t, d_in).astype(x.dtype) * jax.nn.silu(z)
    return routed_linear(y, p, "out_proj", cfg, seed=12), h_T


def forward(params, x, cfg, positions):
    """Scan mamba blocks in groups of `hybrid_attn_every`, applying the ONE
    weight-shared attention block after each full group (deterministic group
    structure — no lax.cond — so dry-run cost extrapolation stays linear).
    Remainder layers (n_layers % every) run without a trailing attn block."""
    from .transformer import rms_norm, dense_block, routed_mlp, scan_layers
    every = cfg.hybrid_attn_every or cfg.n_layers

    from .transformer import _remat_policy
    @functools.partial(jax.checkpoint, policy=_remat_policy(cfg))
    def mamba_body(x, p):
        from .transformer import constrain_batch
        x = constrain_batch(x, cfg)
        y, _ = _ssd_chunk(p, rms_norm(x, p["ln"]), cfg)
        x = x + y
        h2 = rms_norm(x, p["ln2"])
        return x + routed_mlp(h2, p, cfg), None

    n_groups = cfg.n_layers // every
    n_rem = cfg.n_layers - n_groups * every
    grouped, rem = _layer_groups(params["layers"], n_groups, every)

    def group_body(x, pg):
        x, _ = scan_layers(mamba_body, x, pg,
                           unroll=every if cfg.scan_unroll else 1)
        if cfg.hybrid_attn_every > 0:
            x, _ = dense_block(params["shared_attn"], x, cfg,
                               positions=positions, layer_idx=0)
        return x, None

    x, _ = scan_layers(group_body, x, grouped,
                       unroll=n_groups if cfg.scan_unroll else 1)
    if n_rem:
        x, _ = scan_layers(mamba_body, x, rem,
                           unroll=n_rem if cfg.scan_unroll else 1)
    return x


# ------------------------------------------------------------- decode path

def _dummy_kv(cfg, n_groups, b):
    """Inert KV placeholders threaded through the group scan when the hybrid
    shared-attn block is off. The ONE shared helper for prefill and
    decode_step: their leading dim must equal the scanned group count (the
    other scan inputs' leading dim) on BOTH paths, or prefill-built state
    and decode-consumed state drift apart."""
    z = jnp.zeros((n_groups, b, 1, 1, 1), cfg.dtype)
    return z, z


def _layer_groups(layers, n_groups: int, every: int):
    """(grouped, rest): the layer stack cut into (n_groups, every, ...) and
    the remaining layers. Packed plans keep reading the whole tile stack
    in place (core/mapping.split_tile_stacks): only their stack indices
    are cut, so no group or remainder copies a layer's tiles."""
    layers, stacks = split_tile_stacks(layers)
    cut = n_groups * every
    grouped = jax.tree_util.tree_map(
        lambda a: a[:cut].reshape((n_groups, every) + a.shape[1:]), layers)
    rest = jax.tree_util.tree_map(lambda a: a[cut:], layers)
    return join_tile_stacks(grouped, stacks), join_tile_stacks(rest, stacks)


def init_state(cfg, batch, max_len, dtype):
    d = cfg.d_model
    d_in = 2 * d
    nh = d_in // cfg.ssm_head
    st = {
        "h": jnp.zeros((cfg.n_layers, batch, nh, cfg.ssm_state,
                        cfg.ssm_head), jnp.float32),
        "len": jnp.zeros((), jnp.int32),
    }
    if cfg.hybrid_attn_every > 0:
        hd, nkv = cfg.head_dim, cfg.n_kv_heads
        n_attn = cfg.n_layers // cfg.hybrid_attn_every
        st["ak"] = jnp.zeros((n_attn, batch, max_len, nkv, hd), dtype)
        st["av"] = jnp.zeros((n_attn, batch, max_len, nkv, hd), dtype)
    return st


def prefill(params, state, tokens, cfg):
    """Stateful chunked prefill: fills the SSM states and (for the hybrid)
    the shared-attn KV caches over the whole prompt; returns last logits."""
    from .transformer import rms_norm, dense_block, routed_mlp, _softcap, \
        constrain_batch, scan_layers
    x = params["embed"][tokens].astype(cfg.dtype)        # (B,T,d)
    b, t, d = x.shape
    every = cfg.hybrid_attn_every or cfg.n_layers
    pos0 = state["len"]
    positions = pos0 + jnp.arange(t)

    def mamba_body(carry, inp):
        x = carry
        p, h0 = inp
        x = constrain_batch(x, cfg)
        y, h_T = _ssd_chunk(p, rms_norm(x, p["ln"]), cfg, h0=h0)
        x = x + y
        h2 = rms_norm(x, p["ln2"])
        return x + routed_mlp(h2, p, cfg), h_T

    n_groups = cfg.n_layers // every
    n_rem = cfg.n_layers - n_groups * every
    grouped, rem_p = _layer_groups(params["layers"], n_groups, every)
    h_grouped = state["h"][:n_groups * every].reshape(
        (n_groups, every) + state["h"].shape[1:])
    if cfg.hybrid_attn_every > 0:
        ak, av = state["ak"], state["av"]
    else:
        ak, av = _dummy_kv(cfg, n_groups, b)

    def group_body(x, inp):
        pg, hg, ck, cv = inp
        x, h_new = scan_layers(mamba_body, x, (pg, hg),
                               unroll=every if cfg.scan_unroll else 1)
        nk = nv = ck
        if cfg.hybrid_attn_every > 0:
            x, (nk, nv) = dense_block(params["shared_attn"], x, cfg,
                                      positions=positions, layer_idx=0,
                                      cache=(ck, cv), cache_len=pos0)
        return x, (h_new, nk, nv)

    x, (h_all, nak, nav) = scan_layers(
        group_body, x, (grouped, h_grouped, ak, av),
        unroll=n_groups if cfg.scan_unroll else 1)
    h_all = h_all.reshape((n_groups * every,) + state["h"].shape[1:])
    if n_rem:
        x, h_rem = scan_layers(mamba_body, x,
                               (rem_p, state["h"][n_groups * every:]),
                               unroll=n_rem if cfg.scan_unroll else 1)
        h_all = jnp.concatenate([h_all, h_rem], axis=0)
    x = rms_norm(x[:, -1], params["ln_f"])
    unemb = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = _softcap((x @ unemb).astype(jnp.float32), cfg.final_softcap)
    new_state = dict(state, h=h_all, len=pos0 + t)
    if cfg.hybrid_attn_every > 0:
        new_state["ak"], new_state["av"] = nak, nav
    return logits, new_state


def decode_step(params, state, tokens, cfg):
    """Group-structured decode mirroring forward(): `every` mamba steps then
    the shared attention block (with its own KV cache slice per group)."""
    from .transformer import rms_norm, dense_block, routed_mlp, \
        routed_linear, _softcap, scan_layers
    x = params["embed"][tokens[:, 0]].astype(cfg.dtype)   # (B,d)
    b, d = x.shape
    d_in = 2 * d
    n = cfg.ssm_state
    nh = d_in // cfg.ssm_head
    ph = cfg.ssm_head
    every = cfg.hybrid_attn_every or cfg.n_layers
    # scalar on the static path, per-slot (B,) on the pool path — the hybrid
    # shared-attn block then gets batched positions + per-slot cache fill
    pos = state["len"]
    attn_pos = pos[None] if pos.ndim == 0 else pos[:, None]

    def mamba_step(x, inp):
        p, h0 = inp
        xn = rms_norm(x, p["ln"])
        zxbcdt = routed_linear(xn, p, "in_proj", cfg, seed=11)
        z, xin, bm, cm, dt = jnp.split(
            zxbcdt, [d_in, 2 * d_in, 2 * d_in + n, 2 * d_in + 2 * n], -1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        a = -jnp.exp(p["a_log"])
        dec = jnp.exp(a[None] * dt)                        # (B,H)
        xh = xin.reshape(b, nh, ph).astype(jnp.float32)
        h_new = h0 * dec[..., None, None] + jnp.einsum(
            "bn,bh,bhp->bhnp", bm.astype(jnp.float32), dt, xh)
        y = jnp.einsum("bn,bhnp->bhp", cm.astype(jnp.float32), h_new)
        y = y + p["dd"].astype(jnp.float32)[None, :, None] * xh
        y = y.reshape(b, d_in).astype(x.dtype) * jax.nn.silu(z)
        x = x + routed_linear(y, p, "out_proj", cfg, seed=12)
        h2 = rms_norm(x, p["ln2"])
        x = x + routed_mlp(h2, p, cfg)
        return x, h_new

    n_groups = cfg.n_layers // every
    n_rem = cfg.n_layers - n_groups * every
    grouped, rem_p = _layer_groups(params["layers"], n_groups, every)
    h_grouped = state["h"][:n_groups * every].reshape(
        (n_groups, every) + state["h"].shape[1:])

    def group_body(carry, inp):
        x, = carry
        pg, hg, ck, cv = inp
        x, h_new = scan_layers(mamba_step, x, (pg, hg),
                               unroll=every if cfg.scan_unroll else 1)
        nk = nv = ck
        if cfg.hybrid_attn_every > 0:
            y, (nk, nv) = dense_block(params["shared_attn"], x[:, None], cfg,
                                      positions=attn_pos, layer_idx=0,
                                      cache=(ck, cv), cache_len=pos)
            x = y[:, 0]
        return (x,), (h_new, nk, nv)

    if cfg.hybrid_attn_every > 0:
        ak, av = state["ak"], state["av"]
    else:
        ak, av = _dummy_kv(cfg, n_groups, b)
    (x,), (h_all, nak, nav) = scan_layers(
        group_body, (x,), (grouped, h_grouped, ak, av),
        unroll=n_groups if cfg.scan_unroll else 1)
    h_all = h_all.reshape((n_groups * every,) + state["h"].shape[1:])
    if n_rem:
        x, h_rem = scan_layers(mamba_step, x,
                               (rem_p, state["h"][n_groups * every:]),
                               unroll=n_rem if cfg.scan_unroll else 1)
        h_all = jnp.concatenate([h_all, h_rem], axis=0)
    x = rms_norm(x, params["ln_f"])
    unemb = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = _softcap((x @ unemb).astype(jnp.float32), cfg.final_softcap)
    new_state = dict(state, h=h_all, len=pos + 1)
    if cfg.hybrid_attn_every > 0:
        new_state["ak"], new_state["av"] = nak, nav
    return logits, new_state

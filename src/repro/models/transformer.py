"""Unified LM backbone for the assigned architectures.

One config-driven decoder (+optional encoder) covering:
  dense GQA/MQA attention (qwen2, codeqwen, granite, internvl2 backbone),
  QKV bias (qwen family), attn-logit + final-logit softcap and alternating
  local/global sliding-window attention (gemma2), fine-grained MoE with shared
  experts (deepseek-moe, llama4), RWKV-6 time-mix (rwkv6), Mamba-2 SSD blocks
  with shared attention (zamba2), encoder-decoder with cross-attention
  (seamless-m4t), and vision-prefix VLM (internvl2).

Layers are scanned (`scan_layers`, a jax.lax.scan over stacked params whose
packed CIM plans read their tiles in place) with per-layer remat so the
80-layer/400B configs lower to compact HLO and bounded activation memory.
Every linear can be routed through the NeuRRAM CIM path (cim_mode flag) — the
paper's technique as a first-class feature (see cim_linear below).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.mapping import join_tile_stacks, split_tile_stacks
from ..core.quant import pact_quantize
from ..kernels.prng import hash_normal


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str = "dense"
    family: str = "dense"        # dense | moe | rwkv | hybrid | encdec | vlm
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_head: int = 0              # 0 -> d_model // n_heads
    d_ff: int = 1024
    vocab: int = 1024
    qkv_bias: bool = False
    attn_softcap: float = 0.0    # gemma2: 50.0
    final_softcap: float = 0.0   # gemma2: 30.0
    local_window: int = 0        # sliding window size for local layers
    alt_local_global: bool = False  # gemma2: alternate local/global
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_expert: int = 0            # expert FFN width (fine-grained MoE)
    moe_every: int = 1           # llama4: MoE on every 2nd layer
    # SSM / hybrid
    rwkv: bool = False
    ssm_state: int = 0           # mamba2 state dim N
    ssm_head: int = 64           # mamba2 head dim P
    hybrid_attn_every: int = 0   # zamba2: shared attn block period
    # enc-dec
    enc_layers: int = 0
    # vlm
    vis_patches: int = 0         # number of stub vision-prefix embeddings
    # numerics / technique
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    rope_theta: float = 1e6
    # Dry-run accounting: XLA cost_analysis counts while-loop bodies ONCE, so
    # the dry-run lowers with layer/chunk scans fully unrolled (scan_unroll);
    # normal execution keeps scans rolled for compile time.
    scan_unroll: bool = False
    # Explicit activation sharding: tuple of mesh axis names for the batch
    # dim of every residual-stream tensor (e.g. ("pod","data")). Without it
    # GSPMD may propagate FSDP param shardings into activations (replicating
    # tokens and sharding d_model), multiplying compute per device.
    batch_axes: Any = None
    # Dropless MoE dispatch: per-expert capacity = every routed token kept
    # (cap = T). The capacity-factor path makes a token's output depend on
    # which OTHER tokens share the batch (capacity competition) — fine for
    # training throughput, wrong for request-level serving where co-batched
    # requests must not perturb each other. launch/scheduler forces this on.
    moe_dropless: bool = False
    # Perf knobs (EXPERIMENTS.md §Perf):
    remat: str = "minimal"       # minimal (nothing_saveable) | dots
    seq_shard: bool = False      # Megatron-SP: activations seq-sharded on
                                 # 'model' between blocks (AR -> RS+AG)
    moe_impl: str = "sort"       # sort (pjit global dispatch) | ep (shard_map
                                 # all_to_all expert parallelism)
    # NeuRRAM CIM technique (paper): off | noisy (training noise-injection) |
    # chipsim (quantized bit-serial MVM + conductance noise surrogate) |
    # packed (serve dense-block projections through the packed CIM engine —
    # one Pallas dispatch per projection; see models/nn.deploy_transformer_cim)
    cim_mode: str = "off"
    cim_in_bits: int = 4
    cim_out_bits: int = 8
    cim_noise: float = 0.1
    # IR-drop planning constraint for packed deploys: alpha > 0 makes the
    # chip compiler split wide matrices vertically (mapping.ir_drop_max_cols)
    cim_ir_drop: float = 0.0
    # Real-mesh TP serving: the serving Mesh (launch/mesh.serving_mesh)
    # every packed multi-shard projection executes on under shard_map —
    # the prefill/decode jits close over cfg, so they close over the mesh.
    # None keeps the unrolled single-process shard loop
    # (nn.sharded_packed_loop, the parity oracle). jax.sharding.Mesh is
    # hashable, so the config stays usable as a static jit argument.
    cim_mesh: Any = None

    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------- CIM linear

def cim_linear(x, w, cfg: ArchConfig, *, seed: int = 0, packed=None):
    """Route a matmul through the paper's technique, selected by cim_mode.

    off:     plain x @ w.
    noisy:   noise-resilient training forward — Gaussian weight noise at
             cim_noise x max|w| drawn via the stateless hash PRNG (the Pallas
             noisy_matmul kernel implements the same op fused on TPU).
    chipsim: inference surrogate of the chip datapath — PACT-quantized input,
             weight + relaxation-noise, and ADC output quantization. Matches
             the bit-accurate oracle to first order while staying a single
             matmul (the full oracle lives in kernels/cim_mvm/ref.py).
    packed:  the real programmed chip datapath, served by the packed-tile
             executor — `packed` is this projection's (one layer's)
             ShardedPackedLayer (or bare PackedCIMLayer) from
             nn.deploy_transformer_cim; each TP shard's scheduled tile plan
             runs as ONE Pallas dispatch inside the serving jit. With
             cfg.cim_mesh set, multi-shard dispatches run device-resident
             under shard_map with row-parallel partials psum'd over the
             'model' axis (one collective per projection); without a mesh
             the shard loop unrolls in-process (nn.sharded_packed_loop).
    """
    if cfg.cim_mode == "packed" and packed is not None:
        from . import nn as nn_mod
        ccfg = nn_mod.arch_cim_config(cfg)
        shape = x.shape
        y = nn_mod.packed_linear(packed, x.reshape(-1, shape[-1]), ccfg,
                                 seed=seed, mesh=cfg.cim_mesh)
        return y.reshape(*shape[:-1], y.shape[-1]).astype(x.dtype)
    if cfg.cim_mode in ("off", "packed"):
        # packed mode without a deployed plan (encoder, unembed, MoE expert
        # stacks) keeps the float path
        return x @ w
    if cfg.cim_mode == "noisy":
        wmax = jnp.max(jnp.abs(w)).astype(w.dtype)
        eps = hash_normal(w.shape, seed, w.shape[-1]).astype(w.dtype)
        return x @ (w + cfg.cim_noise * wmax * eps)
    if cfg.cim_mode == "chipsim":
        xmax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-6)
        # binary (1-bit) inputs keep one magnitude level, not zero
        n_in = max((1 << (cfg.cim_in_bits - 1)) - 1, 1)
        xq = jnp.round(jnp.clip(x / xmax, -1, 1) * n_in) * (xmax / n_in)
        wmax = jnp.max(jnp.abs(w)).astype(w.dtype)
        eps = hash_normal(w.shape, seed, w.shape[-1]).astype(w.dtype)
        wn = w + cfg.cim_noise * wmax * eps
        y = xq.astype(jnp.float32) @ wn.astype(jnp.float32)
        ymax = jnp.maximum(jnp.max(jnp.abs(y)), 1e-6)
        n_out = max((1 << (cfg.cim_out_bits - 1)) - 1, 1)
        yq = jnp.round(jnp.clip(y / ymax, -1, 1) * n_out) * (ymax / n_out)
        return yq.astype(x.dtype)
    raise ValueError(cfg.cim_mode)


def routed_linear(x, p, name: str, cfg: ArchConfig, *, seed: int = 0):
    """`cim_linear` over `p[name]`, picking up the packed deploy entry
    `p['<name>_cim']` (nn.deploy_transformer_cim / deploy_recurrent_cim)
    when one is present — the routing idiom every model family shares
    (dense blocks, rwkv6 mixes, mamba2 in/out projections)."""
    return cim_linear(x, p[name], cfg, seed=seed,
                      packed=p.get(name + "_cim"))


def scan_layers(body, init, xs, **kw):
    """`lax.scan(body, init, xs, **kw)` over a layer stack whose packed
    plans read their tiles in place — the layer loop every model family
    shares. Each plan's tile stack leaves the scanned operands
    (core/mapping.split_tile_stacks) and is closed over whole, a loop
    invariant; the scan slices only the plan's stack index, so a layer's
    kernels start their DMAs inside the stack instead of on a copy of
    the layer's tiles."""
    xs, stacks = split_tile_stacks(xs)
    return jax.lax.scan(lambda c, x: body(c, join_tile_stacks(x, stacks)),
                        init, xs, **kw)


# ------------------------------------------------------------------- layers

def constrain_batch(x, cfg: "ArchConfig"):
    """Pin the leading (batch) dim of an activation to the data axes; with
    seq_shard also pin dim1 (sequence) to 'model' (sequence parallelism:
    GSPMD then lowers the block-boundary all-reduces to reduce-scatter +
    all-gather pairs, halving activation collective bytes)."""
    if cfg.batch_axes is None:
        return x
    rest = [None] * (x.ndim - 1)
    if cfg.seq_shard and x.ndim >= 3 and x.shape[1] % 16 == 0:
        rest[0] = "model"
    spec = P(tuple(cfg.batch_axes), *rest)
    return jax.lax.with_sharding_constraint(x, spec)


def _remat_policy(cfg: "ArchConfig"):
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * scale


def rope(x, positions, theta: float):
    """x: (..., S, H, D). positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq      # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _softcap(x, cap: float):
    return jnp.tanh(x / cap) * cap if cap > 0 else x


def _attn_mask(q_pos, kv_pos, causal, window, kv_len):
    """Boolean mask, (Sq, Sk) — or (B, Sq, Sk) when q_pos is (B, Sq) and/or
    kv_len is (B,) (slotted-pool decode: every request sits at its own
    position). `window` may be a Python int or traced scalar (0 / false-y
    means no window)."""
    dist = q_pos[..., :, None] - kv_pos[None, :]
    mask = jnp.ones(dist.shape, bool)
    if causal:
        mask &= dist >= 0
    mask &= jnp.where(window > 0, dist < window, True) \
        if isinstance(window, jax.Array) else \
        ((dist < window) if window > 0 else True)
    if kv_len is not None:          # decode: mask beyond current cache fill
        kl = jnp.asarray(kv_len)
        mask &= (kv_pos < kl) if kl.ndim == 0 \
            else kv_pos[None, None, :] < kl[:, None, None]
    return mask


def _expand_mask(mask):
    """Broadcast an (Sq,Sk) or (B,Sq,Sk) mask against (B,H,Sq,Sk) logits."""
    return mask[None, None] if mask.ndim == 2 else mask[:, None]


# KV chunk size above which attention switches to the online-softmax
# (flash-style) path — bounds the logits working set for the 32k/500k cells.
ATTN_CHUNK = 4096


def attention(q, k, v, *, causal: bool, q_pos, kv_pos, window=0,
              softcap: float = 0.0, kv_len: Optional[jax.Array] = None,
              unroll: bool = False):
    """q: (B,Sq,H,D), k/v: (B,Sk,Hkv,D) — GQA via head repetition.

    Short KV: dense softmax. Long KV (prefill_32k / decode_32k / long_500k):
    online-softmax scan over KV chunks — the (Sq, Sk) logits tensor is never
    materialized, peak activation is (Sq, ATTN_CHUNK) per head."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    scale = 1.0 / math.sqrt(d)

    if sk <= 2 * ATTN_CHUNK:
        kf = jnp.repeat(k, rep, axis=2)
        vf = jnp.repeat(v, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf) * scale
        logits = _softcap(logits, softcap)
        mask = _attn_mask(q_pos, kv_pos, causal, window, kv_len)
        logits = jnp.where(_expand_mask(mask), logits.astype(jnp.float32),
                           -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v if rep == 1 else vf)

    # ---- chunked online-softmax path
    nchunks = sk // ATTN_CHUNK
    assert sk % ATTN_CHUNK == 0, f"KV len {sk} not divisible by {ATTN_CHUNK}"
    kc = k.reshape(b, nchunks, ATTN_CHUNK, hkv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunks, ATTN_CHUNK, hkv, d).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(nchunks, ATTN_CHUNK)
    qf = q.astype(jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        kč, vč, posč = inp
        kč = jnp.repeat(kč, rep, axis=2).astype(jnp.float32)
        vč = jnp.repeat(vč, rep, axis=2).astype(jnp.float32)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kč) * scale
        logits = _softcap(logits, softcap)
        mask = _attn_mask(q_pos, posč, causal, window, kv_len)
        logits = jnp.where(_expand_mask(mask), logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vč)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc),
                                  unroll=nchunks if unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def mlp(x, wi, wg, wo, cfg: ArchConfig, seed=0, packed=(None, None, None)):
    """SwiGLU MLP (all assigned dense archs use gated-silu variants).
    packed: optional (w_i, w_g, w_o) PackedCIMLayers (cim_mode="packed")."""
    pi, pg, po = packed
    h = jax.nn.silu(cim_linear(x, wg, cfg, seed=seed, packed=pg)) \
        * cim_linear(x, wi, cfg, seed=seed + 1, packed=pi)
    return cim_linear(h, wo, cfg, seed=seed + 2, packed=po)


def routed_mlp(x, p, cfg: ArchConfig, *, seed: int = 5):
    """`mlp` routed by param name (`w_i/w_g/w_o` + optional `_cim` deploy
    entries) — shared by dense blocks and the mamba2 hybrid MLP."""
    return mlp(x, p["w_i"], p["w_g"], p["w_o"], cfg, seed=seed,
               packed=(p.get("w_i_cim"), p.get("w_g_cim"),
                       p.get("w_o_cim")))


# ------------------------------------------------------------ param init

def _dense_layer_params(key, cfg: ArchConfig, dtype, xattn: bool = False):
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    d, f = cfg.d_model, cfg.d_ff
    ks = iter(jax.random.split(key, 24))
    s = lambda *sh: (jax.random.normal(next(ks), sh) *
                     (1.0 / math.sqrt(sh[0]))).astype(dtype)
    p = {}
    if xattn:
        p["xln"] = jnp.ones((d,), dtype)
        p["xwq"] = s(d, nh * hd)
        p["xwk"] = s(d, nkv * hd)
        p["xwv"] = s(d, nkv * hd)
        p["xwo"] = s(nh * hd, d)
    p["wq"] = s(d, nh * hd)
    p["wk"] = s(d, nkv * hd)
    p["wv"] = s(d, nkv * hd)
    p["wo"] = s(nh * hd, d)
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    p["ln1"] = jnp.ones((d,), dtype)
    p["ln2"] = jnp.ones((d,), dtype)
    if cfg.n_experts > 0:
        de = cfg.d_expert or f
        p["router"] = s(d, cfg.n_experts)
        p["ew_g"] = (jax.random.normal(next(ks), (cfg.n_experts, d, de))
                     / math.sqrt(d)).astype(dtype)
        p["ew_i"] = (jax.random.normal(next(ks), (cfg.n_experts, d, de))
                     / math.sqrt(d)).astype(dtype)
        p["ew_o"] = (jax.random.normal(next(ks), (cfg.n_experts, de, d))
                     / math.sqrt(de)).astype(dtype)
        if cfg.n_shared_experts > 0:
            ds = de * cfg.n_shared_experts
            p["sw_g"] = s(d, ds)
            p["sw_i"] = s(d, ds)
            p["sw_o"] = s(ds, d)
    else:
        p["w_g"] = s(d, f)
        p["w_i"] = s(d, f)
        p["w_o"] = s(f, d)
    return p


def init_params(key, cfg: ArchConfig) -> Dict:
    """Real (materialized) params — for smoke tests at reduced sizes."""
    from . import rwkv6 as rwkv6_mod, mamba2 as mamba2_mod
    dtype = cfg.dtype
    k_emb, k_layers, k_out, k_extra = jax.random.split(key, 4)
    params: Dict = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab, cfg.d_model)) * 0.02
                  ).astype(dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(k_out,
                                               (cfg.d_model, cfg.vocab))
                             * 0.02).astype(dtype)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    if cfg.n_experts > 0 and cfg.moe_every > 1:
        assert cfg.moe_every == 2, "only 1:1 dense/MoE interleave supported"
        n_moe = cfg.n_layers // 2
        cfg_d = cfg.replace(n_experts=0)
        params["dense_layers"] = jax.vmap(
            lambda k: _dense_layer_params(k, cfg_d, dtype))(
                layer_keys[:n_moe])
        params["layers"] = jax.vmap(
            lambda k: _dense_layer_params(k, cfg, dtype))(
                layer_keys[n_moe:2 * n_moe])
        return params
    if cfg.rwkv:
        params["layers"] = jax.vmap(
            lambda k: rwkv6_mod.layer_params(k, cfg, dtype))(layer_keys)
    elif cfg.ssm_state > 0:
        params["layers"] = jax.vmap(
            lambda k: mamba2_mod.layer_params(k, cfg, dtype))(layer_keys)
        if cfg.hybrid_attn_every > 0:   # zamba2 shared attention block
            params["shared_attn"] = _dense_layer_params(k_extra, cfg, dtype)
    else:
        params["layers"] = jax.vmap(
            lambda k: _dense_layer_params(k, cfg, dtype,
                                          xattn=cfg.enc_layers > 0)
        )(layer_keys)
    if cfg.enc_layers > 0:
        enc_keys = jax.random.split(k_extra, cfg.enc_layers)
        params["enc_layers"] = jax.vmap(
            lambda k: _dense_layer_params(k, cfg, dtype))(enc_keys)
        params["ln_enc"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.vis_patches > 0:
        params["vis_proj"] = (jax.random.normal(
            k_extra, (cfg.vis_patches, cfg.d_model)) * 0.02).astype(dtype)
    return params


# ------------------------------------------------------------ layer bodies

def dense_block(p, x, cfg: ArchConfig, *, positions, layer_idx,
                cache=None, cache_len=None, memory=None):
    """One pre-norm transformer block. Returns (y, new_cache)."""
    from . import moe as moe_mod
    x = constrain_batch(x, cfg)
    b, s, d = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    h = rms_norm(x, p["ln1"])
    q = routed_linear(h, p, "wq", cfg, seed=1).reshape(b, s, nh, hd)
    k = routed_linear(h, p, "wk", cfg, seed=2).reshape(b, s, nkv, hd)
    v = routed_linear(h, p, "wv", cfg, seed=3).reshape(b, s, nkv, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(nh, hd)
        k = k + p["bk"].reshape(nkv, hd)
        v = v + p["bv"].reshape(nkv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    # local/global alternation (gemma2): even layers local, odd global
    window = 0
    if cfg.local_window > 0:
        if cfg.alt_local_global:
            is_local = (layer_idx % 2 == 0)
            window = jnp.where(is_local, cfg.local_window, 0) \
                if isinstance(layer_idx, jax.Array) else \
                (cfg.local_window if is_local else 0)
        else:
            window = cfg.local_window

    new_cache = None
    if cache is not None:
        ck, cv = cache                           # (B, S_max, nkv, hd)
        if jnp.ndim(cache_len) == 0:
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k, cache_len, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v, cache_len, axis=1)
        else:                                    # per-slot fill (pool decode)
            sidx = cache_len[:, None] + jnp.arange(s)[None]      # (B, s)
            bidx = jnp.arange(b)[:, None]
            ck = ck.at[bidx, sidx].set(k)
            cv = cv.at[bidx, sidx].set(v)
        kv_pos = jnp.arange(ck.shape[1])
        attn = _attention_window(q, ck, cv, positions, kv_pos, window, cfg,
                                 kv_len=cache_len + s, causal=True)
        new_cache = (ck, cv)
    else:
        kv_pos = positions
        attn = _attention_window(q, k, v, positions, kv_pos, window, cfg,
                                 causal=True)
    x = x + routed_linear(attn.reshape(b, s, nh * hd), p, "wo", cfg, seed=4)

    if memory is not None:                       # cross-attention (enc-dec)
        x = x + _cross_attn(p, x, memory, cfg)

    h2 = rms_norm(x, p["ln2"])
    if "ew_g" in p:                              # MoE FFN (param-keyed so
        # packed CIM serving always takes the sort-based dispatch: only it
        # routes token groups through the per-expert compiled chips — the
        # shard_map EP path would silently fall back to float einsums
        if cfg.moe_impl == "ep" and moe_mod.MESH_FOR_EP is not None \
                and cfg.cim_mode != "packed":
            y = moe_mod.moe_ffn_ep_shardmap(
                p, h2, cfg, moe_mod.MESH_FOR_EP,
                data_axes=tuple(cfg.batch_axes or ("data",)))
        else:
            y = moe_mod.moe_ffn(p, h2, cfg)      # dense/MoE can interleave
    else:
        y = routed_mlp(h2, p, cfg, seed=5)
    return x + y, new_cache


def _attention_window(q, k, v, q_pos, kv_pos, window, cfg, *, causal,
                      kv_len=None):
    """attention() accepts both Python-int and traced window scalars."""
    return attention(q, k, v, causal=causal, q_pos=q_pos, kv_pos=kv_pos,
                     window=window, softcap=cfg.attn_softcap, kv_len=kv_len,
                     unroll=cfg.scan_unroll)


def _cross_attn(p, x, memory, cfg: ArchConfig):
    """Cross-attention used by the enc-dec family (xattn params in p)."""
    b, s, d = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    h = rms_norm(x, p["xln"])
    q = (h @ p["xwq"]).reshape(b, s, nh, hd)
    k = (memory @ p["xwk"]).reshape(b, memory.shape[1], nkv, hd)
    v = (memory @ p["xwv"]).reshape(b, memory.shape[1], nkv, hd)
    rep = nh // nkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * hd)
    return o @ p["xwo"]


# ---------------------------------------------------------------- forward

def _scan_blocks(params, x, cfg: ArchConfig, positions, memory=None):
    """Scan transformer blocks with per-layer remat. When dense_layers is
    present (llama4 1:1 interleave) each scan step is a dense+MoE superblock."""
    interleaved = "dense_layers" in params

    @functools.partial(jax.checkpoint, policy=_remat_policy(cfg))
    def body(x, inp):
        if interleaved:
            (pd, pm), idx = inp
            x, _ = dense_block(pd, x, cfg, positions=positions,
                               layer_idx=2 * idx, memory=memory)
            x, _ = dense_block(pm, x, cfg, positions=positions,
                               layer_idx=2 * idx + 1, memory=memory)
        else:
            p, idx = inp
            x, _ = dense_block(p, x, cfg, positions=positions, layer_idx=idx,
                               memory=memory)
        return x, None

    if interleaved:
        n = cfg.n_layers // 2
        xs = ((params["dense_layers"], params["layers"]), jnp.arange(n))
    else:
        xs = (params["layers"], jnp.arange(cfg.n_layers))
    n_steps = (cfg.n_layers // 2) if interleaved else cfg.n_layers
    x, _ = scan_layers(body, x, xs, unroll=n_steps if cfg.scan_unroll else 1)
    return x


def lm_forward(params, tokens, cfg: ArchConfig, *, vis_embeds=None,
               src_embeds=None):
    """Teacher-forcing forward. tokens: (B, S) int32 -> logits (B, S, V).

    vis_embeds: (B, P, d) stub vision-frontend embeddings (vlm family).
    src_embeds: (B, S_src, d) stub modality-frontend embeddings (encdec).
    """
    from . import rwkv6 as rwkv6_mod, mamba2 as mamba2_mod
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
    if vis_embeds is not None:
        x = jnp.concatenate([vis_embeds.astype(cfg.dtype), x], axis=1)
    x = constrain_batch(x, cfg)
    b, s, _ = x.shape
    positions = jnp.arange(s)

    memory = None
    if cfg.enc_layers > 0:
        assert src_embeds is not None
        memory = _encode(params, src_embeds, cfg)

    if cfg.rwkv:
        x = rwkv6_mod.forward(params["layers"], x, cfg)
    elif cfg.ssm_state > 0:
        x = mamba2_mod.forward(params, x, cfg, positions)
    else:
        x = _scan_blocks(params, x, cfg, positions, memory=memory)

    x = rms_norm(constrain_batch(x, cfg), params["ln_f"])
    unemb = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = x @ unemb
    logits = constrain_batch(logits, cfg)
    logits = _softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if vis_embeds is not None:
        logits = logits[:, vis_embeds.shape[1]:]
    return logits


def _encode(params, src_embeds, cfg: ArchConfig):
    """Bidirectional encoder over frontend embeddings (seamless-m4t)."""
    x = src_embeds.astype(cfg.dtype)
    positions = jnp.arange(x.shape[1])

    @functools.partial(jax.checkpoint, policy=_remat_policy(cfg))
    def body(x, inp):
        p, idx = inp
        h = rms_norm(x, p["ln1"])
        b, s, _ = x.shape
        hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
        q = rope((h @ p["wq"]).reshape(b, s, nh, hd), positions,
                 cfg.rope_theta)
        k = rope((h @ p["wk"]).reshape(b, s, nkv, hd), positions,
                 cfg.rope_theta)
        v = (h @ p["wv"]).reshape(b, s, nkv, hd)
        attn = attention(q, k, v, causal=False, q_pos=positions,
                         kv_pos=positions, softcap=cfg.attn_softcap,
                         unroll=cfg.scan_unroll)
        x = x + attn.reshape(b, s, nh * hd) @ p["wo"]
        h2 = rms_norm(x, p["ln2"])
        return x + mlp(h2, p["w_i"], p["w_g"], p["w_o"], cfg), None

    x, _ = jax.lax.scan(body, x, (params["enc_layers"],
                                  jnp.arange(cfg.enc_layers)),
                        unroll=cfg.enc_layers if cfg.scan_unroll else 1)
    return rms_norm(x, params["ln_enc"])


# ------------------------------------------------------------------- loss

def lm_loss(params, batch, cfg: ArchConfig):
    """batch: dict(tokens (B,S+1), optional vis_embeds/src_embeds)."""
    tokens = batch["tokens"]
    logits = lm_forward(params, tokens[:, :-1], cfg,
                        vis_embeds=batch.get("vis_embeds"),
                        src_embeds=batch.get("src_embeds"))
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ------------------------------------------------------------- serve path

def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    """Decode cache pytree. Attention archs: KV (L,B,S,nkv,hd) pairs.
    rwkv/mamba archs: constant-size recurrent state (the reason the
    long_500k cell is THEIRS to run — see DESIGN.md)."""
    from . import rwkv6 as rwkv6_mod, mamba2 as mamba2_mod
    dtype = dtype or cfg.dtype
    if cfg.rwkv:
        return rwkv6_mod.init_state(cfg, batch, dtype)
    if cfg.ssm_state > 0:
        return mamba2_mod.init_state(cfg, batch, max_len, dtype)
    hd, nkv = cfg.head_dim, cfg.n_kv_heads
    shape = (cfg.n_layers, batch, max_len, nkv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "len": jnp.zeros((), jnp.int32)}


def decode_step(params, cache, tokens, cfg: ArchConfig, memory=None):
    """One decode step: tokens (B, 1) + cache -> (logits (B,V), new cache)."""
    from . import rwkv6 as rwkv6_mod, mamba2 as mamba2_mod
    if cfg.rwkv:
        return rwkv6_mod.decode_step(params, cache, tokens, cfg)
    if cfg.ssm_state > 0:
        return mamba2_mod.decode_step(params, cache, tokens, cfg)

    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
    # cache["len"] is a scalar on the static path and a per-slot (B,) vector
    # on the continuous-batching pool path (launch/scheduler): positions and
    # the kv-fill mask then carry a batch dim, and the cache update scatters
    # at each slot's own fill offset.
    pos = cache["len"]
    positions = pos + jnp.arange(tokens.shape[1]) if pos.ndim == 0 \
        else pos[:, None] + jnp.arange(tokens.shape[1])[None]

    interleaved = "dense_layers" in params

    def body(x, inp):
        if interleaved:
            (pd, pm), ck, cv, idx = inp
            x, (k0, v0) = dense_block(pd, x, cfg, positions=positions,
                                      layer_idx=2 * idx, cache=(ck[0], cv[0]),
                                      cache_len=pos, memory=memory)
            x, (k1, v1) = dense_block(pm, x, cfg, positions=positions,
                                      layer_idx=2 * idx + 1,
                                      cache=(ck[1], cv[1]),
                                      cache_len=pos, memory=memory)
            return x, (jnp.stack([k0, k1]), jnp.stack([v0, v1]))
        p, ck, cv, idx = inp
        y, (nk, nv) = dense_block(p, x, cfg, positions=positions,
                                  layer_idx=idx, cache=(ck, cv),
                                  cache_len=pos, memory=memory)
        return y, (nk, nv)

    if interleaved:
        n = cfg.n_layers // 2
        ck = cache["k"].reshape((n, 2) + cache["k"].shape[1:])
        cv = cache["v"].reshape((n, 2) + cache["v"].shape[1:])
        x, (nks, nvs) = scan_layers(
            body, x, ((params["dense_layers"], params["layers"]), ck, cv,
                      jnp.arange(n)), unroll=n if cfg.scan_unroll else 1)
        nks = nks.reshape((cfg.n_layers,) + nks.shape[2:])
        nvs = nvs.reshape((cfg.n_layers,) + nvs.shape[2:])
    else:
        x, (nks, nvs) = scan_layers(
            body, x, (params["layers"], cache["k"], cache["v"],
                      jnp.arange(cfg.n_layers)),
            unroll=cfg.n_layers if cfg.scan_unroll else 1)
    x = rms_norm(x, params["ln_f"])
    unemb = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = _softcap((x[:, -1] @ unemb).astype(jnp.float32),
                      cfg.final_softcap)
    new_cache = {"k": nks, "v": nvs, "len": pos + tokens.shape[1]}
    return logits, new_cache


def prefill(params, tokens, cache, cfg: ArchConfig, memory=None):
    """Prefill the cache with a full prompt. Attention archs reuse
    decode_step with S>1; recurrent archs use their stateful chunked
    prefill (their decode_step is strictly one-token)."""
    from . import rwkv6 as rwkv6_mod, mamba2 as mamba2_mod
    if cfg.rwkv:
        return rwkv6_mod.prefill(params, cache, tokens, cfg)
    if cfg.ssm_state > 0:
        return mamba2_mod.prefill(params, cache, tokens, cfg)
    return decode_step(params, cache, tokens, cfg, memory=memory)

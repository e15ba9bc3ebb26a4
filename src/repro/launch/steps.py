"""Jit-able train / prefill / decode step functions for the LM stack, plus
the arch-dispatch table (`arch_serving`) the serving driver runs through:
transformer vs rwkv6 vs mamba2 entry points with ONE normalized signature,
so launch/serve.py never hardwires a family's init/prefill/decode/deploy."""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..models import transformer as T
from ..train.optimizer import clip_grads


class ArchServing(NamedTuple):
    """Serving entry points for one architecture, with normalized
    signatures (the model modules order params/state/tokens/cfg
    differently — this table is the single place that absorbs it):

      init_params(key)                      -> params
      init_state(batch, max_len)            -> decode cache / recurrent state
      prefill(params, state, tokens, memory=None)      -> (logits, state)
      decode_step(params, state, tokens, memory=None)  -> (logits, state)
      deploy_cim(key, params, **kw)         -> params with '_cim' engines

    The transformer-vs-rwkv6-vs-mamba2 family dispatch for init/state/
    prefill/decode lives in ONE place — models/transformer's init_params/
    init_cache/prefill/decode_step branch on cfg.rwkv / cfg.ssm_state —
    and this table delegates to it (no second dispatch table to drift).
    deploy_cim is the genuinely family-specific leg and delegates to
    nn.deploy_cim (deploy_transformer_cim for dense/MoE stacks,
    deploy_recurrent_cim for rwkv6/mamba2 — nn.is_recurrent_arch is the
    one predicate), so `serve --cim` works for every family instead of
    dying in the dense-only deploy with an opaque error.

    Real-mesh TP serving threads through cfg, not this table: when the
    driver sets cfg.cim_mesh (serve --cim-mesh), every prefill/decode
    step built from cfg closes over the mesh, deploy_cim places each
    shard's chips on its 'model'-axis device, and the packed dispatches
    run under shard_map (models/nn.sharded_packed_forward).
    """
    init_params: Callable
    init_state: Callable
    prefill: Callable
    decode_step: Callable
    deploy_cim: Callable


def arch_serving(cfg: "T.ArchConfig") -> ArchServing:
    """The serving entry-point table for `cfg` (see ArchServing)."""
    from ..models import nn
    return ArchServing(
        init_params=lambda key: T.init_params(key, cfg),
        init_state=lambda batch, max_len:
            T.init_cache(cfg, batch, max_len, dtype=cfg.dtype),
        prefill=lambda params, state, tokens, memory=None:
            T.prefill(params, tokens, state, cfg, memory=memory),
        decode_step=lambda params, state, tokens, memory=None:
            T.decode_step(params, state, tokens, cfg, memory=memory),
        deploy_cim=lambda key, params, **kw:
            nn.deploy_cim(key, params, cfg, **kw))


def adamw_init_f32(params):
    """Optimizer state in f32 regardless of (bf16) param dtype."""
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree_util.tree_map(z, params),
            "v": jax.tree_util.tree_map(z, params),
            "t": jnp.zeros((), jnp.int32)}


def adamw_apply(grads, state, params, lr, b1=0.9, b2=0.999, eps=1e-8,
                weight_decay=0.01):
    t = state["t"] + 1
    up = {}
    m = jax.tree_util.tree_map(
        lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
        state["m"], grads)
    v = jax.tree_util.tree_map(
        lambda v_, g: b2 * v_ + (1 - b2)
        * jnp.square(g.astype(jnp.float32)), state["v"], grads)
    bc1 = 1 - b1 ** t.astype(jnp.float32)
    bc2 = 1 - b2 ** t.astype(jnp.float32)
    new_params = jax.tree_util.tree_map(
        lambda p, m_, v_: (p.astype(jnp.float32)
                           - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
                                   + weight_decay * p.astype(jnp.float32))
                           ).astype(p.dtype),
        params, m, v)
    return new_params, {"m": m, "v": v, "t": t}


def make_train_step(cfg: T.ArchConfig, lr: float = 1e-4, accum: int = 1,
                    grad_spec=None, data_axes=None, mesh=None,
                    grad_sync: str = "micro"):
    """Microbatched gradient-accumulation train step.

    accum > 1 splits the global batch into `accum` microbatches scanned
    sequentially — activation memory scales 1/accum (how the 4k-seq train
    cells fit HBM). grad_spec (a pytree of PartitionSpec) applies a ZeRO-style
    sharding constraint to the accumulated gradients, so each microbatch's
    gradients are reduce-scattered instead of living replicated."""
    def train_step(params, opt_state, batch):
        if accum == 1:
            loss, grads = jax.value_and_grad(T.lm_loss)(params, batch, cfg)
        else:
            def micro(carry, mb):
                loss_sum, g_acc = carry
                l, g = jax.value_and_grad(T.lm_loss)(params, mb, cfg)
                g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                if grad_spec is not None and grad_sync == "micro":
                    g = jax.tree_util.tree_map(
                        jax.lax.with_sharding_constraint, g, grad_spec)
                return (loss_sum + l, g), None

            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)
            if data_axes and mesh is not None:
                # the (accum, micro, ...) reshape must keep the microbatch dim
                # sharded over the data axes, else activations replicate
                from jax.sharding import NamedSharding, PartitionSpec
                mbs = jax.tree_util.tree_map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, PartitionSpec(
                            None, data_axes, *([None] * (x.ndim - 2))))),
                    mbs)
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if grad_spec is not None:
                g0 = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, g0, grad_spec)
            (loss, grads), _ = jax.lax.scan(micro, (jnp.zeros(()), g0), mbs)
            if grad_spec is not None and grad_sync == "once":
                grads = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, grads, grad_spec)
            loss = loss / accum
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
        grads, gnorm = clip_grads(grads, 1.0)
        params, opt_state = adamw_apply(grads, opt_state, params, lr)
        return params, opt_state, loss, gnorm
    return train_step


def make_prefill_step(cfg: T.ArchConfig):
    sv = arch_serving(cfg)

    def prefill_step(params, cache, batch):
        memory = None
        if cfg.enc_layers > 0:
            memory = T._encode(params, batch["src_embeds"], cfg)
        tokens = batch["tokens"]
        if cfg.vis_patches > 0:
            # vision prefix enters the cache first (stubbed frontend embeds)
            emb = batch["vis_embeds"]
            logits, cache = _prefix_embeds(params, cache, emb, cfg)
        return sv.prefill(params, cache, tokens, memory=memory)
    return prefill_step


def _prefix_embeds(params, cache, emb, cfg):
    """Run raw embeddings (no token lookup) through the decoder into cache."""
    # reuse decode_step by temporarily treating embeds as pre-embedded input:
    # simplest faithful route: map embeds through the same block scan
    pos = cache["len"]
    positions = pos + jnp.arange(emb.shape[1])

    def body(x, inp):
        p, ck, cv, idx = inp
        y, (nk, nv) = T.dense_block(p, x, cfg, positions=positions,
                                    layer_idx=idx, cache=(ck, cv),
                                    cache_len=pos)
        return y, (nk, nv)

    x, (nks, nvs) = T.scan_layers(
        body, emb.astype(cfg.dtype),
        (params["layers"], cache["k"], cache["v"],
         jnp.arange(cfg.n_layers)),
        unroll=cfg.n_layers if cfg.scan_unroll else 1)
    logits = None
    return logits, {"k": nks, "v": nvs, "len": pos + emb.shape[1]}


def make_decode_step(cfg: T.ArchConfig):
    sv = arch_serving(cfg)

    def decode_step(params, cache, batch):
        memory = batch.get("memory") if isinstance(batch, dict) else None
        tokens = batch["tokens"] if isinstance(batch, dict) else batch
        return sv.decode_step(params, cache, tokens, memory=memory)
    return decode_step


# ------------------------------------------------- slotted pool (scheduler)

# Bookkeeping leaves the continuous-batching pool adds ON TOP of the arch's
# native cache/state pytree (launch/scheduler.init_pool). They live INSIDE
# the donated pytree so occupancy changes mutate array values, never pytree
# structure — the decode jit traces exactly once.
#   active: (B,) bool   slot is decoding (free-slot bitmap = ~active)
#   tok:    (B,1) int32 each slot's last emitted token (decode input)
# The arch-native "len" leaf is widened from a scalar to a per-slot (B,)
# vector; models/transformer.decode_step branches on its ndim.
POOL_KEYS = ("active", "tok")


def _split_pool(pool):
    """pool -> (arch-native cache/state view, active, tok)."""
    native = {k: v for k, v in pool.items() if k not in POOL_KEYS}
    return native, pool["active"], pool["tok"]


def make_pool_decode_step(cfg: T.ArchConfig):
    """One decode step over the WHOLE slot pool: (params, pool) ->
    (logits (B,V), pool). Every slot steps through the model (the compiled
    chips are weight-stationary — one dispatch serves all in-flight
    requests); inactive slots are then frozen by a select against the
    `active` bitmap, so their state is bit-identical across steps and the
    emitted token / fill length only advance for live requests."""
    sv = arch_serving(cfg)

    def step(params, pool):
        native, active, tok = _split_pool(pool)
        logits, new = sv.decode_step(params, native, tok)
        out = {}
        for k, n in new.items():
            old = native[k]
            if k == "len":                       # (B,) per-slot fill
                out[k] = jnp.where(active, n, old)
            else:                                # slot dim is axis 1
                m = active.reshape((1, -1) + (1,) * (n.ndim - 2))
                out[k] = jnp.where(m, n, old)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out["tok"] = jnp.where(active[:, None], nxt, tok)
        out["active"] = active
        return logits, out
    return step


def make_slot_prefill_step(cfg: T.ArchConfig):
    """One prefill CHUNK into a single slot: (params, pool, tokens (1,C),
    slot) -> (logits (1,V), pool). The slot's state is sliced out of the
    pool (every cache/state leaf keeps the slot dim at axis 1 — the layout
    invariant distributed/sharding.cache_pspecs already relies on), run
    through the arch's EXISTING chunked prefill with a scalar fill length,
    and written back at the slot offset. The slot index is traced, so all
    chunks of one length share one trace; the chunk logits' argmax lands in
    pool['tok'] so the final chunk seeds the slot's first decode token."""
    sv = arch_serving(cfg)

    def chunk_step(params, pool, tokens, slot):
        native, active, tok = _split_pool(pool)
        view = {k: (v[slot] if k == "len"
                    else jax.lax.dynamic_slice_in_dim(v, slot, 1, axis=1))
                for k, v in native.items()}
        logits, view = sv.prefill(params, view, tokens)
        out = {k: (native["len"].at[slot].set(v) if k == "len"
                   else jax.lax.dynamic_update_slice_in_dim(
                       native[k], v, slot, axis=1))
               for k, v in view.items()}
        first = jnp.argmax(logits[0]).astype(jnp.int32)
        out["tok"] = tok.at[slot, 0].set(first)
        out["active"] = active
        return logits, out
    return chunk_step

"""Mixture-of-Experts FFN (deepseek-moe fine-grained shared+routed, llama4).

Two execution paths:

  * moe_ffn (default, pjit-friendly): sort-based dispatch with per-expert
    capacity — tokens are replicated top_k times, sorted by expert id, sliced
    into fixed-capacity per-expert groups (capacity = tokens*top_k/E * slack),
    run through a batched expert einsum, and combined by scatter-add. No
    (T, E, C) one-hot dispatch tensor is ever materialized, and the expert
    einsum shards expert-parallel over the 'model' mesh axis.
  * moe_ffn_ep_shardmap: explicit expert-parallel shard_map with
    lax.all_to_all over the 'model' axis (tokens travel to expert owners and
    back). Used by the perf hillclimb to compare XLA-chosen vs hand-written
    collective schedules.

The NeuRRAM mapping note (DESIGN.md section 4): routed experts are the
datacenter-scale analogue of the chip's selectively power-gated CIM cores —
top-k routing activates k of E weight-stationary arrays, exactly the paper's
multi-core granularity argument. With cim_mode == "packed" that analogy is
executed literally: each (layer, expert) has its own compiled chip
(nn.deploy_transformer_cim), and the capacity-grouped dispatch below routes
every expert's token group through that expert's scheduled packed Pallas
dispatch (`_expert_matmul`); shared-expert projections ride the same
cim_linear path as dense blocks.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.mapping import slice_tile_stacks, take

# set by the launcher before tracing when cfg.moe_impl == "ep"
MESH_FOR_EP = None


def _router(x2, router_w, top_k: int):
    """x2: (T, d) -> (weights (T,k), experts (T,k)) with softmax over top-k."""
    logits = x2.astype(jnp.float32) @ router_w.astype(jnp.float32)
    gate, idx = jax.lax.top_k(logits, top_k)            # (T, k)
    gate = jax.nn.softmax(gate, axis=-1)
    return gate, idx


def _expert_matmul(p: Dict, name: str, xe, cfg, *, seed: int = 0):
    """Batched expert matmul (E, C, d) @ (E, d, f) -> (E, C, f), routed
    through each expert's packed CIM chip when one is deployed
    (p['<name>_cim'], leading E dim) — E packed dispatches, one per
    power-gated expert chip — and the float einsum otherwise.

    With cfg.cim_mesh set (real-mesh TP serving) and E divisible by the
    'model' axis, the expert loop runs EXPERT-PARALLEL under shard_map:
    each device holds its E/m experts' chips (placed at deploy time,
    expert dim on 'model') and dispatches only its own token groups; the
    out-spec all-gather reassembles the (E, C, f) stack — the datacenter
    rendering of the paper's power-gated core selection. Per-expert seeds
    follow the global expert id either way, so the mesh path is
    bitwise-equal to the unrolled loop."""
    pcl = p.get(name + "_cim")
    if pcl is None or getattr(cfg, "cim_mode", "off") != "packed":
        return jnp.einsum("ecd,edf->ecf", xe, p[name])
    from . import nn as nn_mod
    ccfg = nn_mod.arch_cim_config(cfg)
    mesh = getattr(cfg, "cim_mesh", None)
    m = dict(mesh.shape).get("model", 1) if mesh is not None else 1
    if m > 1 and cfg.n_experts % m == 0:
        e_local = cfg.n_experts // m

        def shard_fn(pcl_loc, xe_loc):
            base = jax.lax.axis_index("model") * e_local
            ys = []
            for el in range(e_local):
                pe = jax.tree_util.tree_map(lambda a: a[el], pcl_loc)
                ys.append(nn_mod.packed_linear(pe, xe_loc[el], ccfg,
                                               seed=seed + base + el))
            return jnp.stack(ys)

        fn = jax.shard_map(shard_fn, mesh=mesh,
                           in_specs=(P("model"), P("model")),
                           out_specs=P("model"), check_vma=False)
        return fn(slice_tile_stacks(pcl), xe).astype(xe.dtype)
    ys = []
    for e in range(cfg.n_experts):
        pe = take(pcl, e)
        ys.append(nn_mod.packed_linear(pe, xe[e], ccfg, seed=seed + e))
    return jnp.stack(ys).astype(xe.dtype)


def moe_ffn(p: Dict, x, cfg, capacity_factor: float = 1.25):
    """x: (B, S, d) -> (B, S, d). Sort-based capacity-padded dispatch."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    x2 = x.reshape(t, d)

    gate, idx = _router(x2, p["router"], k)             # (T,k)
    flat_e = idx.reshape(-1)                            # (T*k,)
    flat_g = gate.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), k)               # token id per slot

    order = jnp.argsort(flat_e)                         # stable sort by expert
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]

    if getattr(cfg, "moe_dropless", False):
        # Dropless: capacity = one slot per token per expert (top_k indices
        # are distinct, so an expert sees each token at most once). No token
        # is ever dropped -> a token's output no longer depends on which
        # other tokens share the batch. Required by the continuous-batching
        # pool (launch/scheduler), where co-batched requests must be
        # bitwise-independent.
        cap = t
    else:
        cap = min(max(int(math.ceil(t * k / e * capacity_factor)), 4), t * k)
    # position of each sorted slot within its expert group
    ones = jnp.ones_like(se)
    pos_in_e = jnp.cumsum(ones) - 1
    start = jnp.searchsorted(se, jnp.arange(e))          # (E,)
    pos_in_e = pos_in_e - start[se]
    keep = pos_in_e < cap                                # capacity drop

    # gather tokens into (E, C, d)
    slot = jnp.where(keep, se * cap + pos_in_e, e * cap)  # overflow -> dump row
    xe = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(x2[st])
    xe = xe[:-1].reshape(e, cap, d)

    # batched expert FFN: (E,C,d) @ (E,d,de) -> shards expert-parallel
    # (or, packed: one CIM dispatch per routed expert chip)
    h = jax.nn.silu(_expert_matmul(p, "ew_g", xe, cfg, seed=11)) \
        * _expert_matmul(p, "ew_i", xe, cfg, seed=211)
    ye = _expert_matmul(p, "ew_o", h, cfg, seed=411)     # (E,C,d)

    # combine: weighted scatter-add back to tokens
    ye_flat = ye.reshape(e * cap, d)
    contrib = ye_flat[jnp.where(keep, se * cap + pos_in_e, 0)] \
        * (sg * keep)[:, None].astype(x.dtype)
    y2 = jnp.zeros((t, d), x.dtype).at[st].add(contrib)

    if cfg.n_shared_experts > 0:
        if getattr(cfg, "cim_mode", "off") == "packed":
            # packed serving only: noisy/chipsim training modes keep the
            # exact float matmuls shared experts always used
            from .transformer import cim_linear
            hs = jax.nn.silu(cim_linear(x2, p["sw_g"], cfg, seed=611,
                                        packed=p.get("sw_g_cim"))) \
                * cim_linear(x2, p["sw_i"], cfg, seed=612,
                             packed=p.get("sw_i_cim"))
            y2 = y2 + cim_linear(hs, p["sw_o"], cfg, seed=613,
                                 packed=p.get("sw_o_cim"))
        else:
            hs = jax.nn.silu(x2 @ p["sw_g"]) * (x2 @ p["sw_i"])
            y2 = y2 + hs @ p["sw_o"]
    return y2.reshape(b, s, d)


def moe_ffn_ep_shardmap(p: Dict, x, cfg, mesh, capacity_factor: float = 1.25,
                        data_axes=("pod", "data"), model_axis="model"):
    """Explicit EP: experts sharded over `model_axis`; each device routes its
    local tokens and all_to_all's them to the expert owners.

    x sharded P(data_axes, None, None); expert weights P(model_axis, ...).
    Float path only — packed CIM serving routes through moe_ffn's sort
    dispatch instead (transformer.dense_block forces this), since only that
    path drives the per-expert compiled chips.
    """
    axes = [a for a in data_axes if a in mesh.axis_names]
    ep = mesh.shape[model_axis]
    e_local = cfg.n_experts // ep
    k = cfg.top_k

    def local_fn(router_w, ew_g, ew_i, ew_o, x_loc):
        # x_loc: (b_l, s_loc, d) — tokens SEQ-SHARDED over the model axis so
        # dispatch work is not replicated across the row (a replicated-x
        # variant was 16x compute — refuted, see §Perf)
        b_l, s, d = x_loc.shape
        t = b_l * s
        x2 = x_loc.reshape(t, d)
        gate, idx = _router(x2, router_w, k)
        flat_e = idx.reshape(-1)
        flat_g = gate.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t), k)
        dest = flat_e // e_local                          # owner device
        order = jnp.argsort(dest * cfg.n_experts + flat_e)
        se, st, sg = flat_e[order], flat_t[order], flat_g[order]
        sd = dest[order]
        cap = int((t * k / ep) * capacity_factor) or 1
        ones = jnp.ones_like(sd)
        pos = jnp.cumsum(ones) - 1
        start = jnp.searchsorted(sd, jnp.arange(ep))
        pos = pos - start[sd]
        keep = pos < cap
        slot = jnp.where(keep, sd * cap + pos, ep * cap)
        send = jnp.zeros((ep * cap + 1, d + 2), x_loc.dtype)
        payload = jnp.concatenate(
            [x2[st], (se + 1)[:, None].astype(x_loc.dtype),   # 0 = padding
             sg[:, None].astype(x_loc.dtype)], -1)
        send = send.at[slot].set(payload)[:-1].reshape(ep, cap, d + 2)
        recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # receiver-side sort-dispatch: group recv slots by LOCAL expert id,
        # capacity-padded — each local expert computes only its own tokens
        # (the earlier masked-one-hot variant computed every token against
        # every local expert: e_local x overcompute, refuted in §Perf)
        ec = ep * cap
        xr = recv[..., :d].reshape(ec, d)
        er = recv[..., d].astype(jnp.int32).reshape(ec)    # 0 = pad
        my_first = jax.lax.axis_index(model_axis) * e_local
        el = jnp.where(er > 0, er - 1 - my_first, e_local)  # pad -> overflow
        order2 = jnp.argsort(el)
        el_s = el[order2]
        cap_l = max(int(ec / e_local * 1.25), 4)
        ones2 = jnp.ones_like(el_s)
        pos2 = jnp.cumsum(ones2) - 1
        start2 = jnp.searchsorted(el_s, jnp.arange(e_local))
        pos2 = pos2 - start2[jnp.clip(el_s, 0, e_local - 1)]
        keep2 = (pos2 < cap_l) & (el_s < e_local)
        slot2 = jnp.where(keep2, el_s * cap_l + pos2, e_local * cap_l)
        xe = jnp.zeros((e_local * cap_l + 1, d), x_loc.dtype)
        xe = xe.at[slot2].set(xr[order2])[:-1].reshape(e_local, cap_l, d)
        h = jax.nn.silu(jnp.einsum("etd,edf->etf", xe, ew_g)) \
            * jnp.einsum("etd,edf->etf", xe, ew_i)
        ye = jnp.einsum("etf,efd->etd", h, ew_o).reshape(e_local * cap_l, d)
        contrib2 = ye[jnp.where(keep2, el_s * cap_l + pos2, 0)] \
            * keep2[:, None].astype(x_loc.dtype)
        yr = jnp.zeros((ec, d), x_loc.dtype).at[order2].set(contrib2)
        yr = yr.reshape(ep, cap, d)
        back = jax.lax.all_to_all(yr, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        back2 = back.reshape(ep * cap, d)
        contrib = back2[jnp.where(keep, sd * cap + pos, 0)] \
            * (sg * keep)[:, None].astype(x_loc.dtype)
        y2 = jnp.zeros((t, d), x_loc.dtype).at[st].add(contrib)
        return y2.reshape(b_l, s, d)

    seq_ok = x.shape[1] % ep == 0
    xspec = P(tuple(axes), model_axis if seq_ok else None, None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(model_axis), P(model_axis), P(model_axis), xspec),
        out_specs=xspec,
        check_vma=False)
    y = fn(p["router"], p["ew_g"], p["ew_i"], p["ew_o"], x)
    if cfg.n_shared_experts > 0:
        b, s, d = x.shape
        x2 = x.reshape(-1, d)
        hs = jax.nn.silu(x2 @ p["sw_g"]) * (x2 @ p["sw_i"])
        y = y + (hs @ p["sw_o"]).reshape(b, s, d)
    return y

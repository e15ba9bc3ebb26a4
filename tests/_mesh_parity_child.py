"""Child process for tests/test_mesh_serving.py: shard_map-vs-unrolled
parity on a REAL 8-device mesh.

Runs under XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
parent before spawning — the flag must land before jax first initializes,
which is why this is a subprocess and not an in-process test: the rest of
the suite must keep seeing the real device count). Prints ONE json dict on
stdout; the parent asserts on it.

Contract checked per plan variant (plain / merged-core scheduled / IR-drop
column-split) and per partition (col = wq, row = wo, none = the
8-indivisible w_g):

  * the shard_map executor (`nn.sharded_packed_forward(mesh=...)`) is
    BITWISE-equal to the unrolled-loop oracle (`nn.sharded_packed_loop`),
    both jit'd — the row-parallel reduction via the default
    row_reduce='ordered' (all_gather + `nn._ordered_fold`; `lax.psum`'s
    reduction order is backend-defined, which is exactly why 'ordered'
    exists). The 'psum' lowering is additionally smoke-checked to CLOSE
    (1-ulp-scale) agreement — it is allowed to differ in the last ulp;
  * the shard_map trace costs exactly ONE packed-kernel trace per plan
    (the loop costs one per shard) and repeated calls cost zero;
  * deploy-time placement: multi-shard stacks are device-resident
    (not fully replicated) with the shard axis on 'model';
  * MoE expert dispatch: `_expert_matmul` under the mesh (expert-parallel
    shard_map) is bitwise-equal to the unrolled expert loop;
  * whole steps: a 2-layer packed prefill + decode step under the mesh,
    whose layer scan (`transformer.scan_layers`) hands each layer's plans
    the whole tile stack and an index, which shard_map cuts back to the
    layer's tiles (`core.mapping.slice_tile_stacks`), is bitwise-equal to
    the same steps with the mesh off — dense tensor-parallel and MoE
    expert-parallel.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
import repro.models.nn as nn
import repro.models.transformer as T
from repro.core.types import CoreSpec
from repro.kernels.cim_mvm.kernel import TRACE_COUNTS
from repro.launch.mesh import serving_mesh

PROJS = ("wq", "wo", "w_g")           # col / row / none (d_ff=255)


def packed_traces():
    return TRACE_COUNTS["cim_mvm_packed"] + TRACE_COUNTS["cim_mvm_scheduled"]


def check_variant(tag, cfg, spec, mesh, out):
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    p = nn.deploy_transformer_cim(jax.random.PRNGKey(7), params, cfg,
                                  mode="ideal", spec=spec, mesh=mesh)
    ccfg = nn.arch_cim_config(cfg)
    res = {}
    for pi, name in enumerate(PROJS):
        spl = p["layers"][name + "_cim"]
        # layer 0 of the (L, n_shards, ...) stack — what lax.scan serves
        spl0 = nn.ShardedPackedLayer(
            jax.tree_util.tree_map(lambda a: a[0], spl.shards),
            spl.partition, spl.n_shards)
        r = {"partition": spl.partition, "n_shards": spl.n_shards,
             "n_passes": spl0.shards.packed.n_passes,
             "placed": (not spl0.shards.packed.gd_tiles
                        .sharding.is_fully_replicated)}
        x = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(1), pi),
                              (4, params["layers"][name].shape[1]))
        part, nsh = spl.partition, spl.n_shards
        f_loop = jax.jit(lambda s, xx, part=part, nsh=nsh:
                         nn.sharded_packed_loop(
                             nn.ShardedPackedLayer(s, part, nsh), xx, ccfg))
        f_mesh = jax.jit(lambda s, xx, part=part, nsh=nsh:
                         nn.sharded_packed_forward(
                             nn.ShardedPackedLayer(s, part, nsh), xx, ccfg,
                             mesh=mesh))
        y_loop = np.asarray(f_loop(spl0.shards, x))
        t0 = packed_traces()
        y_mesh = np.asarray(f_mesh(spl0.shards, x))
        r["mesh_traces_first"] = packed_traces() - t0
        t0 = packed_traces()
        y_mesh2 = np.asarray(f_mesh(spl0.shards, x))
        r["mesh_traces_repeat"] = packed_traces() - t0
        r["bitwise"] = bool((y_loop == y_mesh).all())
        r["deterministic"] = bool((y_mesh == y_mesh2).all())
        if part == "row":
            # the lax.psum lowering stays functional: close to the
            # ordered fold (its backend-defined order may drift 1 ulp)
            y_psum = np.asarray(jax.jit(
                lambda s, xx, part=part, nsh=nsh:
                nn.sharded_packed_forward(
                    nn.ShardedPackedLayer(s, part, nsh), xx, ccfg,
                    mesh=mesh, row_reduce="psum"))(spl0.shards, x))
            r["psum_close"] = bool(np.allclose(y_psum, y_mesh,
                                               rtol=1e-6, atol=1e-5))
        res[name] = r
    out[tag] = res


def check_moe(mesh, out):
    from repro.models.moe import _expert_matmul
    cfg = configs.get("deepseek-moe-16b", smoke=True).replace(
        dtype=jnp.float32, cim_mode="packed", n_layers=1)
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    cfg_mesh = cfg.replace(cim_mesh=mesh)
    p = nn.deploy_transformer_cim(jax.random.PRNGKey(9), params, cfg_mesh,
                                  mode="ideal")
    p0 = jax.tree_util.tree_map(lambda a: a[0], p["layers"])
    xe = jax.random.normal(jax.random.PRNGKey(3),
                           (cfg.n_experts, 4, cfg.d_model))
    y_loop = np.asarray(jax.jit(
        lambda pp, xx: _expert_matmul(pp, "ew_g", xx, cfg, seed=11))(p0, xe))
    y_mesh = np.asarray(jax.jit(
        lambda pp, xx: _expert_matmul(pp, "ew_g", xx, cfg_mesh,
                                      seed=11))(p0, xe))
    out["moe"] = {
        "bitwise": bool((y_loop == y_mesh).all()),
        "placed": (not p["layers"]["ew_g_cim"].packed.gd_tiles
                   .sharding.is_fully_replicated)}


def check_steps(mesh, out):
    for tag, cfg in (
            ("dense", configs.get("gemma2-9b", smoke=True).replace(
                dtype=jnp.float32, cim_mode="packed", n_layers=2,
                d_ff=256)),
            ("moe", configs.get("deepseek-moe-16b", smoke=True).replace(
                dtype=jnp.float32, cim_mode="packed", n_layers=2))):
        cfg_mesh = cfg.replace(cim_mesh=mesh)
        params = nn.deploy_transformer_cim(
            jax.random.PRNGKey(7), T.init_params(jax.random.PRNGKey(0), cfg),
            cfg_mesh, mode="ideal")
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                  cfg.vocab)
        runs, n_shard_maps = [], 0
        for c in (cfg, cfg_mesh):
            prefill = jax.jit(lambda p, t, s, c=c: T.prefill(p, t, s, c))
            decode = jax.jit(lambda p, s, t, c=c: T.decode_step(p, s, t, c))
            lg_p, cache = prefill(params, toks[:, :-1],
                                  T.init_cache(c, 2, 16, dtype=c.dtype))
            lg_d, cache = decode(params, cache, toks[:, -1:])
            runs.append([np.asarray(a) for a in
                         jax.tree_util.tree_leaves((lg_p, lg_d, cache))])
            n_shard_maps = str(jax.make_jaxpr(decode)(
                params, cache, toks[:, -1:])).count("shard_map[")
        out["step_" + tag] = {
            "bitwise": all((a == b).all() for a, b in zip(*runs)),
            "finite": bool(np.isfinite(runs[1][1]).all()),
            "mesh_shard_maps": n_shard_maps}


def main():
    out = {"device_count": jax.device_count()}
    mesh = serving_mesh()
    out["mesh_shape"] = dict(mesh.shape)
    base = configs.get("gemma2-9b", smoke=True).replace(
        dtype=jnp.float32, cim_mode="packed", n_layers=1, d_ff=255)
    check_variant("plain", base, None, mesh, out)
    # d_model 256 on a 4-core chip: the per-shard projection set overflows
    # the cores, so the planner merges (time-shares) them -> multi-pass
    # scheduled plans through the pass-major kernel under shard_map
    # (d_ff 256 divides the 8-wide axis, so w_g rides 'col' here; the
    # 'none' fallback is covered by the plain/irdrop variants)
    check_variant("sched", base.replace(d_model=256, d_head=64, d_ff=256),
                  CoreSpec(n_cores=4), mesh, out)
    check_variant("irdrop", base.replace(cim_ir_drop=2e-7), None, mesh, out)
    check_moe(mesh, out)
    check_steps(mesh, out)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Chip smoke: serve codeqwen1.5-7b at its published widths through the
packed CIM path on a TPU, and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip tensor-parallel phase only

One chip. `repro.launch.serve.main` keeps 2 of the config's 32 layers (a
depth cut; every width is published: d_model 4096, 32 MHA heads of 128,
d_ff 13440, vocab 92416), compiles every projection of those layers onto
simulated NeuRRAM cores, and serves 8 requests (prompts of 32-128 tokens
in 32-token chunks, up to 16 generated tokens) through the continuous-
batching engine. One layer splits into 7,120 tiles of 128x256 weight
cells, far beyond a 48-core NeuRRAM chip, so the core budget is raised to
8,192. Memory: f32 weights 0.93 GB a layer, programmed conductances
1.87 GB, packed tiles 0.93 GB — 3.7 GB a layer — plus 3.0 GB of untied
embeddings: 10.5 GB for two layers. Measured on a TPU v5e (16 GB), two
layers peak at 12.66 GB with deploy transients, so two layers it is; a
third would not fit.
Checks: the decode step compiles to a Mosaic kernel (`tpu_custom_call`),
the decode step traced once, every request got its tokens, every logit is
finite, and the packed kernel agrees with the per-tile oracle
(`mapping.multicore_mvm` over `ref.cim_mvm_ref`) on one real projection.

Four chips. The same requests on one layer, tensor-parallel over a 1x4
('data', 'model') mesh (`--cim-mesh auto`: each shard's chips on its own
device, dispatched under shard_map), compared in the same process with
`--cim-mesh off` — the unrolled shard loop on one device, the oracle.

The last line of standard output is the JSON result. With no TPU, or when
any phase fails, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "codeqwen1.5-7b"
CORES = 8192          # >= one layer's 7,120 tiles at published widths
LAYERS = 2            # 12.66 GB peak of 16 GB on a v5e (see above)
TRAFFIC = ["--arch", ARCH, "--cim", "--cim-cores", str(CORES), "--traffic",
           "--requests", "8", "--slots", "8", "--prompt-len", "128",
           "--chunk", "32", "--gen", "16", "--capture-logits"]
# Kernel check bound. Both sides compute each tile's charge in f32; a
# tile's ADC count can only differ where that charge lies within a few
# ulp of a decision step (|q|/v_decr + 0.5 near an integer), which happens
# for well under 0.1% of counts — and each such flip moves an output's
# sum of 32 row-tile counts by one. A kernel that rounded conductances to
# bf16 (8-bit mantissa, ~0.4% charge error) would flip a large share.
MAX_DIFFERING_SHARE = 0.01
MAX_COUNT_DIFF = 2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    print(f"  ok: {what}", flush=True)


def layer_tiles(cfg) -> int:
    """Tiles the planner makes of one layer's packed projections."""
    import jax
    from repro.core.mapping import MatrixReq, plan_layers
    from repro.core.types import CoreSpec
    from repro.models import nn
    from repro.models import transformer as T
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg))["layers"]
    reqs = [MatrixReq(n, *shapes[n].shape[1:])
            for n in nn.PACKED_PROJ_KEYS if n in shapes]
    return len(plan_layers(reqs, CoreSpec(n_cores=CORES)).tiles)


def describe(layers: int) -> None:
    from repro import configs
    cfg = configs.get(ARCH)
    tiles = layer_tiles(cfg)
    print(f"config: {ARCH}, {layers} of {cfg.n_layers} layers (depth cut), "
          f"d_model={cfg.d_model} heads={cfg.n_heads}x{cfg.head_dim} "
          f"kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab}")
    print(f"tiles per layer: {tiles}; core budget {CORES} "
          f"(a 48-core NeuRRAM chip cannot hold one layer)")
    check(tiles <= CORES, f"core budget {CORES} holds one layer's {tiles} "
          "tiles")


def serve(argv):
    from repro.launch import serve as serve_mod
    return serve_mod.main(argv)


def check_served(run, n_requests: int, vocab: int) -> None:
    import numpy as np
    st, reqs = run.stats, run.requests
    print(f"deploy: {st['deploy_s']:.3f} s; served {st['requests']} "
          f"requests, {st['tokens']} tokens, decode_traces="
          f"{st['decode_traces']}")
    check(st["requests"] == n_requests, f"{n_requests} requests served")
    check(all(len(r.tokens) == r.max_new for r in reqs),
          "every request got its max_new tokens")
    check(st["tokens"] == sum(r.max_new for r in reqs), "token count")
    check(st["decode_traces"] == 1, "decode_traces == 1")
    rows = np.concatenate([np.stack(r.logits) for r in reqs])
    check(rows.shape == (st["tokens"], vocab),
          f"one logits row of {vocab} per token")
    check(bool(np.isfinite(rows).all()), "all logits finite")


def kernel_check(run) -> None:
    """Packed dispatch vs the per-tile oracle on wq of layer 0: summed ADC
    counts over the served plan's tiles, conductances and ADC steps."""
    import jax
    import numpy as np
    from repro.core import mapping
    from repro.core.quant import quantize_to_int
    from repro.kernels.cim_mvm.ref import cim_mvm_ref
    from repro.models.nn import arch_cim_config
    cfg = run.engine.cfg
    ccfg = arch_cim_config(cfg)
    spl = run.engine.params["layers"]["wq_cim"]
    pcl = jax.tree_util.tree_map(lambda a: a[0, 0], spl.shards)
    layer, pk = pcl.layer, pcl.packed
    gp, gn = layer.g_pos, layer.g_neg
    n_rows, n_cols = gp.shape
    tiles = [mapping.Tile("wq", row0=rb * pk.bk, col0=cb * pk.bn,
                          rows=min(pk.bk, n_rows - rb * pk.bk),
                          cols=min(pk.bn, n_cols - cb * pk.bn))
             for rb, cb in zip(pk.row_block, pk.col_block)]
    v_decr = np.asarray(pk.v_decr_tiles)
    # the served tiles re-packed with count semantics (fold_norm=False):
    # the same kernel, summing raw ADC counts over row splits
    counts_pack = mapping.pack_tiles(tiles, gp - gn, gsum=gp + gn,
                                     v_decr=v_decr)
    x = layer.in_alpha * 0.5 * jax.random.truncated_normal(
        jax.random.PRNGKey(11), -2.0, 2.0, (32, n_rows))
    x_int, _ = quantize_to_int(x, layer.in_alpha, ccfg.in_bits)
    got = np.asarray(mapping.multicore_mvm_packed(x_int, counts_pack, ccfg))
    step = {(t.row0, t.col0): v for t, v in zip(tiles, v_decr)}

    def core(xt, _w, t):
        sl = lambda a: jax.lax.dynamic_slice(a, (t.row0, t.col0),
                                             (t.rows, t.cols))
        return cim_mvm_ref(xt, sl(gp), sl(gn), step[(t.row0, t.col0)],
                           ccfg, bit_serial=False).counts.astype(np.float32)

    with jax.default_matmul_precision("highest"):
        want = np.asarray(mapping.multicore_mvm(x_int, gp - gn, tiles, core))
    diff = np.abs(got - want)
    share = float((diff > 0).mean())
    print(f"kernel check (wq, layer 0, {len(tiles)} tiles, 32x{n_rows} "
          f"inputs): {share:.6f} of summed ADC counts differ, largest "
          f"difference {diff.max():.0f}; counts span "
          f"[{want.min():.0f}, {want.max():.0f}]")
    check(share <= MAX_DIFFERING_SHARE and diff.max() <= MAX_COUNT_DIFF,
          f"at most {MAX_DIFFERING_SHARE:.0%} of counts differ, by at most "
          f"{MAX_COUNT_DIFF} (flips only within rounding of a decision "
          "step)")


def peak_bytes(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def one_chip() -> None:
    import jax
    describe(LAYERS)
    run = serve(TRAFFIC + ["--layers", str(LAYERS)])
    hlo = run.engine.decode_hlo()
    check("tpu_custom_call" in hlo,
          "compiled decode step calls the Mosaic kernels (tpu_custom_call: "
          f"{hlo.count('tpu_custom_call')} sites)")
    check_served(run, 8, run.engine.cfg.vocab)
    print(f"peak_bytes_in_use after serving: {peak_bytes(jax.devices())[0]}")
    kernel_check(run)


def shard_devices(run) -> None:
    """Each 'model' shard of every sharded chip stack on its own device."""
    import jax
    from repro.models.nn import ShardedPackedLayer
    devs = set(jax.devices())
    n = 0
    for name, spl in sorted(run.engine.params["layers"].items()):
        if not isinstance(spl, ShardedPackedLayer) or spl.n_shards == 1:
            continue
        placed = []
        for leaf in jax.tree_util.tree_leaves(spl.shards):
            where = {s.device: s.index[1].start
                     for s in leaf.addressable_shards}
            placed.append(set(where) == devs
                          and sorted(where.values()) == list(range(4)))
        check(all(placed), f"{name}: shards 0..3 each on their own device "
              f"({len(placed)} arrays)")
        n += 1
    check(n > 0, "some projections are sharded over 'model'")


def four_chips() -> None:
    import jax
    import numpy as np
    devices = jax.devices()
    check(len(devices) == 4, "four devices")
    describe(1)
    argv = TRAFFIC + ["--layers", "1"]
    run = serve(argv + ["--cim-mesh", "auto"])
    check(dict(run.engine.cfg.cim_mesh.shape) == {"data": 1, "model": 4},
          "mesh {'data': 1, 'model': 4}")
    shard_devices(run)
    check_served(run, 8, run.engine.cfg.vocab)
    print(f"peak_bytes_in_use per device after the mesh run: "
          f"{peak_bytes(devices)}")
    mesh_tokens = [r.tokens for r in run.requests]
    mesh_rows = np.concatenate([np.stack(r.logits) for r in run.requests])
    del run
    gc.collect()
    run = serve(argv + ["--cim-mesh", "off"])
    check_served(run, 8, run.engine.cfg.vocab)
    print(f"peak_bytes_in_use per device after the loop run: "
          f"{peak_bytes(devices)}")
    loop_rows = np.concatenate([np.stack(r.logits) for r in run.requests])
    check([r.tokens for r in run.requests] == mesh_tokens,
          "greedy tokens identical: shard_map mesh vs shard loop")
    diff = np.abs(mesh_rows - loop_rows)
    bitwise = mesh_rows.tobytes() == loop_rows.tobytes()
    print(f"logits shard_map vs loop: bitwise_equal={bitwise}, largest "
          f"difference {diff.max():.9g} (logits span "
          f"{np.abs(loop_rows).max():.6g})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip tensor-parallel phase")
    args = ap.parse_args(argv)
    from repro.launch.env import enable_compile_cache
    enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit("chip_smoke: FAILED: no TPU found")
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

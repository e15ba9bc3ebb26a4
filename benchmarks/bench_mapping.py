"""Tile-plan executor harness: per-tile loop vs packed vs scheduled dispatch.

Times one layer's multi-core CIM MVM through (a) the legacy Python loop of
per-tile kernels (`multicore_mvm`, one dynamic_slice matmul per tile),
(b) the packed executor (`multicore_mvm_packed`, the whole plan as one
pallas_call over a tile grid) and (c) the SCHEDULED executor (the same plan
forced through the pass-major grid kernel that serializes merged cores),
across three plan shapes plus a genuinely merged (multi-pass) plan, plus a
recurrent-stack entry: an rwkv6 layer's eight projections compiled as one
chip and served packed, timed against the float matmuls they replace — and
a bidirectional entry: the RBM's jit'd packed Gibbs scan (one compiled
chip, alternating fwd + transpose-direction dispatches) timed against the
per-matrix compat loop it replaced (gibbs_packed_* vs gibbs_compat_*) —
and real-mesh TP rows (mesh_shardmap_* vs mesh_unrolled_*): one TP-sharded
projection's forward through the device-resident shard_map executor vs the
unrolled in-process shard loop, measured in a child process on 8 forced
host devices (bench_mesh_child.py, bitwise parity asserted there).

The merged (multi-pass) plan additionally carries the fused-reduction perf
claim: sched_fused_* (the default in-kernel run accumulation) vs
sched_partial_* (fused=False, the pre-fusion per-slot-partial baseline) on
a serving-sized batch, both bitwise-checked against the per-tile loop
oracle; the block-shape autotuner then sweeps bm candidates on the same
plan with the SAME timer (autotune_*_bm* rows, derived=1 marks the winner)
and sched_tuned_* re-times the serving path (bm=None) after the cache is
primed. precision_serve_b{1..8} rows serve one compiled matrix at every
bit-serial input precision (paper Fig. 1d from the serving path): the
derived column is a dict of the analytic NeuRRAM energy/latency model at
that operating point (core/energy.py) plus the measured relative error.

The derived column otherwise reports how many kernel jit traces the
executor cost — every packed path's headline is ONE trace/dispatch per plan
regardless of tile count. That trace-count contract is deterministic and
always enforced (sched_fused_/sched_partial_ rows included); the
"scheduled no slower than 2x packed on unmerged plans" ratio and the
"fused strictly faster than partial on merged plans" gate are reported as
warnings by default (shared CI machines make timing gates flaky) and only
fail the run under --enforce-timing (the dedicated bench job).

CLI (the CI bench-smoke step):

    python -m benchmarks.bench_mapping --quick --out BENCH_mapping.json
"""
import argparse
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp

from repro.core.types import CIMConfig, CoreSpec
from repro.core.conductance import weights_to_conductances
from repro.core.mapping import (MatrixReq, plan_layers, pack_tiles,
                                schedule_tiles, multicore_mvm,
                                multicore_mvm_packed)
from repro.kernels.cim_mvm.ops import cim_mvm
from repro.kernels.cim_mvm.kernel import TRACE_COUNTS

from ._timing import best_of as _time

# (name, weight rows, cols) — 1 tile; 3x2=6 tiles; 4x3=12 tiles
SHAPES = [("1tile", 100, 60), ("6tile", 300, 500), ("12tile", 500, 700)]
# merged-plan case: forced onto a tiny chip -> multi-pass schedule
MERGED = ("merged", 300, 500, 3)


def run(quick: bool = False):
    cfg = CIMConfig(in_bits=4, out_bits=8)
    n_rep = 3 if quick else 5
    shapes = SHAPES[:2] if quick else SHAPES
    out = []
    for name, r, c in shapes:
        k = jax.random.PRNGKey(0)
        w = jax.random.normal(k, (r, c)) * 0.1
        cond = weights_to_conductances(w, cfg.device)
        x = jax.random.randint(jax.random.fold_in(k, 1), (16, r), -7, 8)
        vd = 0.002
        tiles = plan_layers([MatrixReq("m", r, c)]).tiles_for("m")
        packed = pack_tiles(tiles, cond.g_pos - cond.g_neg,
                            gsum=cond.g_pos + cond.g_neg, v_decr=vd)
        sched = pack_tiles(tiles, cond.g_pos - cond.g_neg,
                           gsum=cond.g_pos + cond.g_neg, v_decr=vd,
                           schedule=schedule_tiles(tiles))

        def loop_exec(xx):
            def matmul_fn(xt, _wt, t):
                gp = jax.lax.dynamic_slice(cond.g_pos, (t.row0, t.col0),
                                           (t.rows, t.cols))
                gn = jax.lax.dynamic_slice(cond.g_neg, (t.row0, t.col0),
                                           (t.rows, t.cols))
                return cim_mvm(xt, gp, gn, vd, cfg)
            return multicore_mvm(xx, cond.g_pos - cond.g_neg, tiles,
                                 matmul_fn)

        t0 = TRACE_COUNTS["cim_mvm"]
        us_loop = _time(lambda: loop_exec(x), n_rep)
        tr_loop = TRACE_COUNTS["cim_mvm"] - t0

        t0 = TRACE_COUNTS["cim_mvm_packed"]
        us_packed = _time(lambda: multicore_mvm_packed(x, packed, cfg),
                          n_rep)
        tr_packed = TRACE_COUNTS["cim_mvm_packed"] - t0

        # the same single-pass plan FORCED through the pass-major kernel:
        # scheduling must cost nothing on unmerged plans
        t0 = TRACE_COUNTS["cim_mvm_scheduled"]
        us_sched = _time(lambda: multicore_mvm_packed(x, sched, cfg,
                                                      scheduled=True), n_rep)
        tr_sched = TRACE_COUNTS["cim_mvm_scheduled"] - t0

        y_loop = loop_exec(x)
        assert bool(jnp.all(y_loop == multicore_mvm_packed(x, packed, cfg))), \
            f"packed != loop on {name}"
        assert bool(jnp.all(y_loop == multicore_mvm_packed(
            x, sched, cfg, scheduled=True))), f"scheduled != loop on {name}"
        out.append((f"mapping_loop_{name}_t{len(tiles)}",
                    round(us_loop, 1), tr_loop))
        out.append((f"mapping_packed_{name}_t{len(tiles)}",
                    round(us_packed, 1), tr_packed))
        out.append((f"mapping_sched_{name}_t{len(tiles)}",
                    round(us_sched, 1), tr_sched))

    # merged multi-pass plan: scheduled kernel is the ONLY packed executor.
    # The fused run layout (in-kernel accumulation wherever the schedule's
    # visit order allows) is the default; fused=False forces the pre-fusion
    # per-slot-partial baseline — the sched_fused_ vs sched_partial_ pair is
    # the perf claim of the fusion, gated below (strictly faster).
    mname, r, c, n_cores = MERGED
    k = jax.random.PRNGKey(2)
    w = jax.random.normal(k, (r, c)) * 0.1
    cond = weights_to_conductances(w, cfg.device)
    x = jax.random.randint(jax.random.fold_in(k, 1), (16, r), -7, 8)
    tiles = plan_layers([MatrixReq("m", r, c)],
                        CoreSpec(n_cores=n_cores)).tiles_for("m")
    vd = 0.002
    sched = pack_tiles(tiles, cond.g_pos - cond.g_neg,
                       gsum=cond.g_pos + cond.g_neg, v_decr=vd,
                       schedule=schedule_tiles(tiles))
    t0 = TRACE_COUNTS["cim_mvm_scheduled"]
    us = _time(lambda: multicore_mvm_packed(x, sched, cfg), n_rep)
    tr = TRACE_COUNTS["cim_mvm_scheduled"] - t0
    # fused-vs-partial pair on a serving-sized batch (more reduction work =
    # more signal for the strictly-faster gate)
    xb = jax.random.randint(jax.random.fold_in(k, 9), (256, r), -7, 8)
    t0 = TRACE_COUNTS["cim_mvm_scheduled"]
    us_fused = _time(lambda: multicore_mvm_packed(xb, sched, cfg), n_rep)
    tr_fused = TRACE_COUNTS["cim_mvm_scheduled"] - t0
    t0 = TRACE_COUNTS["cim_mvm_scheduled"]
    us_part = _time(lambda: multicore_mvm_packed(xb, sched, cfg, fused=False),
                    n_rep)
    tr_part = TRACE_COUNTS["cim_mvm_scheduled"] - t0

    def loop_merged(xx):
        def matmul_fn(xt, _wt, t):
            gp = jax.lax.dynamic_slice(cond.g_pos, (t.row0, t.col0),
                                       (t.rows, t.cols))
            gn = jax.lax.dynamic_slice(cond.g_neg, (t.row0, t.col0),
                                       (t.rows, t.cols))
            return cim_mvm(xt, gp, gn, vd, cfg)
        return multicore_mvm(xx, cond.g_pos - cond.g_neg, tiles, matmul_fn)

    y_loop = loop_merged(x)
    assert bool(jnp.all(y_loop == multicore_mvm_packed(x, sched, cfg))), \
        "fused scheduled != loop on merged plan"
    assert bool(jnp.all(y_loop == multicore_mvm_packed(
        x, sched, cfg, fused=False))), "partial scheduled != loop on merged"
    tag = f"{mname}_p{sched.n_passes}_t{sched.n_tiles}"
    out.append((f"mapping_sched_{tag}", round(us, 1), tr))
    out.append((f"sched_fused_{tag}", round(us_fused, 1), tr_fused))
    out.append((f"sched_partial_{tag}", round(us_part, 1), tr_part))

    # block-shape autotune on the merged plan: sweep bm candidates with the
    # SAME timer as every row here, cache the winner (ops.packed_call picks
    # it up on every later bm=None call for this plan signature)
    from repro.kernels.cim_mvm import autotune
    winner, sweeps = autotune.tune(
        xb.astype(jnp.float32), sched, activation=cfg.activation,
        n_max=cfg.out_mag_levels, v_read=cfg.v_read,
        timer=lambda f: _time(f, n_rep), refresh=True)
    for bm, us_bm in sorted(sweeps.items()):
        out.append((f"autotune_{tag}_bm{bm}", round(us_bm, 1),
                    int(bm == winner)))
    # the serving path (bm=None) now picks the tuned winner up via lookup
    us_tuned = _time(lambda: multicore_mvm_packed(xb, sched, cfg), n_rep)
    out.append((f"sched_tuned_{tag}", round(us_tuned, 1), winner))

    # recurrent projection stack (rwkv6 smoke geometry): one layer's whole
    # time-mix + channel-mix projection set compiled as ONE chip
    # (nn.deploy_recurrent_cim granularity) and served as one packed
    # dispatch per projection — timed against the float matmuls the packed
    # path replaces (the recurrent serving surface's perf trajectory)
    from repro.core.cim import compile_chip, packed_forward
    d, dff = 128, 256
    kr = jax.random.PRNGKey(3)
    rnames = ("wr", "wk", "wv", "wg", "wo", "ck", "cv", "cr")
    rshapes = {"ck": (d, dff), "cv": (dff, d)}
    ws = {n: 0.1 * jax.random.normal(jax.random.fold_in(kr, i),
                                     rshapes.get(n, (d, d)))
          for i, n in enumerate(rnames)}
    chip = compile_chip(jax.random.PRNGKey(4), ws, cfg, CoreSpec(),
                        "ideal", in_alpha=2.0)
    xs = {n: jax.random.normal(jax.random.fold_in(kr, 100 + i),
                               (16, ws[n].shape[0]))
          for i, n in enumerate(rnames)}

    # inputs/weights enter as traced jit arguments (like every other entry
    # here) — a constant closure would let XLA fold the float baseline away
    @jax.jit
    def packed_stack(xs_):
        return [packed_forward(chip.layers[n], xs_[n], cfg) for n in rnames]

    @jax.jit
    def float_stack(xs_, ws_):
        return [xs_[n] @ ws_[n] for n in rnames]

    t0 = TRACE_COUNTS["cim_mvm_packed"] + TRACE_COUNTS["cim_mvm_scheduled"]
    us_packed = _time(lambda: packed_stack(xs), n_rep)
    tr = (TRACE_COUNTS["cim_mvm_packed"]
          + TRACE_COUNTS["cim_mvm_scheduled"]) - t0
    us_float = _time(lambda: float_stack(xs, ws), n_rep)
    out.append((f"recurrent_packed_rwkv6stack_m{len(rnames)}",
                round(us_packed, 1), tr))
    out.append((f"recurrent_float_rwkv6stack_m{len(rnames)}",
                round(us_float, 1), 0))

    # bidirectional RBM Gibbs serving (paper Fig. 4e-g): the jit'd packed
    # scan loop — ONE compiled chip, alternating fwd + transpose-direction
    # dispatches — against the retired per-matrix compat loop
    # (cim_api.program/forward with a hand-built transposed CIMLayer) it
    # replaced. Benchmarks are the one sanctioned place that still drives
    # the compat wrappers as a baseline (tests/test_bidirectional.py
    # audits src/repro itself).
    from repro.core import cim as cim_api
    from repro.core.cim import CIMLayer
    from repro.core.calibration import calibrate_layer
    from repro.core.quant import quantize_to_int
    from repro.models import nn as NN, rbm as RBM
    from repro.data import binary_patterns, corrupt_flip
    n_vis, n_hid, pix, cycles = 138, 32, 128, 5
    params = RBM.init(jax.random.PRNGKey(5), n_vis=n_vis, n_hid=n_hid)
    v = binary_patterns(jax.random.PRNGKey(6), 64, d=pix, rank=4)
    v_c, mask = corrupt_flip(jax.random.PRNGKey(7), v, 0.2, pixels=pix)
    rcfg = CIMConfig(in_bits=2, out_bits=8)
    crbm = NN.deploy_rbm_cim(jax.random.PRNGKey(8), params, rcfg, v[:32],
                             mode="ideal")
    t0 = (TRACE_COUNTS["cim_mvm_packed"]
          + TRACE_COUNTS["cim_mvm_transposed"])
    us_gibbs = _time(lambda: RBM.chip_gibbs_recover(
        jax.random.PRNGKey(9), crbm, v_c, mask, n_cycles=cycles), n_rep)
    tr = (TRACE_COUNTS["cim_mvm_packed"]
          + TRACE_COUNTS["cim_mvm_transposed"]) - t0

    w_aug = RBM._augmented(params)
    fwd = cim_api.program(jax.random.PRNGKey(10), w_aug, rcfg, in_alpha=1.0,
                          x_cal=RBM._aug_v(v[:32]), mode="ideal")
    g_pos_t, g_neg_t = fwd.g_pos.T, fwd.g_neg.T
    ph = jax.nn.sigmoid(v[:32] @ params["w"] + params["b"])
    h_int, _ = quantize_to_int(RBM._aug_h((ph > 0.5).astype(jnp.float32)),
                               1.0, rcfg.in_bits, signed=True)
    cal = calibrate_layer(jax.random.PRNGKey(11), h_int, g_pos_t, g_neg_t,
                          rcfg)
    bwd = CIMLayer(g_pos_t, g_neg_t, fwd.w_max,
                   jnp.sum(g_pos_t + g_neg_t, axis=0), cal.v_decr,
                   cal.adc_offset, jnp.asarray(1.0, jnp.float32))

    def compat_loop():
        vcur, pv = v_c, v_c
        for i in range(cycles):
            kh, kv = jax.random.split(
                jax.random.fold_in(jax.random.PRNGKey(9), i))
            lh = cim_api.forward(fwd, RBM._aug_v(vcur), rcfg,
                                 seed=2 * i)[:, :n_hid]
            h = jax.random.bernoulli(
                kh, jax.nn.sigmoid(lh)).astype(jnp.float32)
            lv = cim_api.forward(bwd, RBM._aug_h(h), rcfg,
                                 seed=2 * i + 1)[:, :n_vis]
            pv = jax.nn.sigmoid(lv)
            vcur = jnp.where(mask, v_c,
                             jax.random.bernoulli(kv, pv).astype(jnp.float32))
        return pv

    us_compat = _time(compat_loop, n_rep)
    out.append((f"gibbs_packed_rbm_c{cycles}", round(us_gibbs, 1), tr))
    out.append((f"gibbs_compat_rbm_c{cycles}", round(us_compat, 1), 0))
    out.extend(_precision_rows(n_rep))
    out.extend(_mesh_rows())
    return out


def _precision_rows(n_rep):
    """Bit-serial precision scaling (paper Fig. 1d) FROM THE SERVING PATH:
    one matrix compiled and served packed at every input precision 1..8.
    Each row's derived column is a dict — the analytic NeuRRAM per-MVM
    model at that operating point (core/energy.py: energy, latency,
    TOPS/W, 1024-dim EDP) next to the measured serve time and the measured
    relative error vs the float matmul. The 1-bit row costs the same model
    energy as 2-bit (both are one input phase — binary inputs skip the
    bit-serial loop entirely); accuracy is what the knob trades away."""
    from repro.core.cim import compile_chip, packed_forward
    from repro.core.energy import neurram_edp
    rows = []
    k = jax.random.PRNGKey(13)
    w = 0.1 * jax.random.normal(k, (140, 200))
    xf = jax.random.normal(jax.random.fold_in(k, 1), (64, 140))
    y_ref = xf @ w
    for bits in range(1, 9):
        pcfg = CIMConfig(in_bits=bits, out_bits=8)
        chip = compile_chip(jax.random.PRNGKey(14), {"m": w}, pcfg,
                            CoreSpec(), "ideal", in_alpha=2.0)
        fwd = jax.jit(lambda xx, _l=chip.layers["m"], _c=pcfg:
                      packed_forward(_l, xx, _c))
        us = _time(lambda: fwd(xf), n_rep)
        y = fwd(xf)
        rel = float(jnp.linalg.norm(y - y_ref) / jnp.linalg.norm(y_ref))
        edp, cost = neurram_edp(bits, 8)
        rows.append((f"precision_serve_b{bits}", round(us, 1), {
            "energy_pj": round(float(cost.energy_pj), 2),
            "latency_model_ns": round(float(cost.latency_ns), 2),
            "tops_per_w": round(float(cost.tops_per_w), 3),
            "edp_1024": float(edp),
            "rel_err": round(rel, 4),
        }))
    return rows


def _mesh_rows():
    """Real-mesh TP serving rows: shard_map vs unrolled executors for one
    TP-sharded projection stack, measured in a CHILD process on 8 forced
    host devices (bench_mesh_child.py). A subprocess because the forced
    device count must precede jax init, and this process's single-device
    rows must keep their real backend for run-to-run comparability. The
    child asserts shard_map/unrolled bitwise parity before timing."""
    from repro.launch.env import require_cpu_parent
    require_cpu_parent("the mesh rows")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = pathlib.Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = str(repo / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, str(repo / "benchmarks" / "bench_mesh_child.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise SystemExit("bench_mesh_child failed:\n" + proc.stderr[-4000:])
    return [tuple(r) for r in
            json.loads(proc.stdout.strip().splitlines()[-1])]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI bench-smoke: fewer shapes/reps")
    ap.add_argument("--out", default=None,
                    help="write rows as JSON (perf trajectory seed)")
    ap.add_argument("--enforce-timing", action="store_true",
                    help="fail (not just warn) when the scheduled dispatch "
                         "exceeds 2x the packed kernel on unmerged plans — "
                         "for the dedicated bench job, not the shared fast "
                         "tier where wall-clock gates flake")
    args = ap.parse_args(argv)
    rows = run(quick=args.quick)
    print("name,us_per_call,derived")
    for name, us, d in rows:
        dcol = json.dumps(d, sort_keys=True) if isinstance(d, dict) else d
        print(f"{name},{us},{dcol}")
    if args.out:
        payload = {name: ({"us_per_call": us, **d} if isinstance(d, dict)
                          else {"us_per_call": us, "traces": d})
                   for name, us, d in rows}
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.out}")
    # deterministic contract (always enforced): every packed/scheduled
    # executor costs exactly ONE kernel trace per plan shape — the
    # shard_map executor and the fused/partial scheduled pair included
    # (each variant of the merged plan traces once; the pair costs two
    # traces total because fused=False is a different jit signature)
    for name, _, tr in rows:
        if name.startswith(("mapping_packed_", "mapping_sched_",
                            "sched_fused_", "sched_partial_",
                            "mesh_shardmap_")) and tr != 1:
            raise SystemExit(
                f"packed-executor trace contract broken on {name}: "
                f"{tr} traces (expected 1)")
    # advisory wall-clock ratio: scheduled dispatch vs the packed kernel on
    # unmerged plans (2x headroom; warning unless --enforce-timing)
    by = {name.rsplit("_t", 1)[0]: us for name, us, _ in rows}
    for tag in [n for n in by if n.startswith("mapping_packed_")]:
        stag = tag.replace("mapping_packed_", "mapping_sched_")
        if stag in by and by[stag] > 2.0 * by[tag]:
            msg = (f"scheduled dispatch regressed vs packed on {tag}: "
                   f"{by[stag]:.1f}us vs {by[tag]:.1f}us")
            if args.enforce_timing:
                raise SystemExit(msg)
            print(f"WARNING: {msg}")
    # fused-reduction perf gate: in-kernel run accumulation must beat the
    # per-slot-partial baseline on merged plans — strictly, that is the
    # point of the fusion (warning unless --enforce-timing)
    us_by_name = {name: us for name, us, _ in rows}
    for name, us in us_by_name.items():
        if not name.startswith("sched_fused_"):
            continue
        pus = us_by_name.get(name.replace("sched_fused_", "sched_partial_"))
        if pus is not None and not us < pus:
            msg = (f"fused reduction not faster on {name}: "
                   f"{us:.1f}us fused vs {pus:.1f}us partial")
            if args.enforce_timing:
                raise SystemExit(msg)
            print(f"WARNING: {msg}")
    return rows


if __name__ == "__main__":
    main()

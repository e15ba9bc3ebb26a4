"""Serving driver: static-batch and continuous-batching request serving.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
      --batch 4 --prompt-len 64 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --smoke \
      --cim --traffic --requests 8 --slots 4
  python -m repro.launch.serve --arch codeqwen1.5-7b --layers 2 --cim \
      --cim-cores 8192 --traffic      # published widths, 2 of 32 layers

--layers N keeps the first N layers of a config and every width: the
depth cut for serving a published-width model on one accelerator.
`main` returns the generated tokens in static mode and a `TrafficRun`
(stats including deploy seconds, the engine, the requests) in --traffic
mode.

Two serving modes share one compiled chip stack (weight-stationary: the
same programmed conductances serve every request):

  * default (static batch): one fixed request batch is prefilled once,
    then decoded token-by-token in lockstep (greedy) with the cache
    updated in place (donated). Both the prefill and decode jits are
    timed through repro.obs.clock.timed_call — block_until_ready
    around each step, warmup (compile) excluded from the per-token stats.
  * --traffic (continuous batching): an open-loop Poisson request stream
    (data/synthetic.traffic_requests — mixed prompt lengths, per-request
    generation budgets) drives launch/scheduler.ContinuousBatchingEngine:
    a slotted KV/state pool with request admission + eviction between
    decode steps and chunked prefill interleaved with decode. Reports
    p50/p99 token latency, TTFT and tokens/sec. The decode jit traces
    ONCE across all occupancy changes (enforced here: trace count is
    printed and asserted).

On the production mesh the cache/pool shards (slot dim over data axes)
per distributed/sharding.py (cache_pspecs / pool_pspecs).

--cim routes every packed-servable projection (dense blocks, shared experts,
MoE routed-expert stacks, AND the recurrent stacks — rwkv6 time/channel
mixes, mamba2 in/out + hybrid MLP + the one shared attention block) through
the chip compiler (core.cim.compile_chip): each layer's weights run the full
plan -> schedule -> program -> calibrate -> pack pipeline once before
serving, and every projection then executes as one scheduled Pallas dispatch
per TP shard inside the prefill/decode jits — chip-sim inference as a
serving scenario, not a per-layer demo. Entry points come from the
normalized table launch/steps.arch_serving — init/state/prefill/decode
delegate to the family dispatch in models/transformer, and deploy_cim
picks deploy_transformer_cim vs deploy_recurrent_cim — so `--cim --arch
rwkv6-7b` / `zamba2-7b` serve instead of dying in the dense-only
deploy. The TP width comes from the ACTUAL serving mesh
(launch/mesh.serving_mesh): one engine per 'model'-axis shard.

--cim-mesh picks HOW the shards execute (real-mesh TP serving):
'auto' (default) builds the real Mesh over the local devices, places each
shard's compiled chip stack on its own 'model'-axis device at deploy time,
and runs every multi-shard packed dispatch device-resident under shard_map
— row-parallel partials meet in one lax.psum, column-parallel slices in
the out-spec all-gather; the prefill/decode jits close over the mesh via
cfg.cim_mesh. 'off' keeps the documented single-process unrolled shard
loop (nn.sharded_packed_loop, the parity oracle); 'DxM' (e.g. '1x8')
forces an explicit (data, model) mesh shape. On one device both modes
collapse to the same single-dispatch path. Every mesh comes from
launch/mesh.make_mesh (Auto axes). Multi-device CPU smoke:
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tools/ci.sh).
--cim-ir-drop > 0 turns on the IR-drop planning constraint (vertical
column splits); --cim-cores shrinks the per-chip core budget to force
merged-core (seq-slot scheduled) plans; --cim-bits N (1..8) recompiles
and serves the whole chip at N-bit bit-serial input precision — the
paper's Fig. 1d precision-reconfigurability as a serving knob (the arch
config is the one source of truth: deploy and the serving jits derive the
same CIMConfig from it via models/nn.arch_cim_config).

Multi-process scale-out (launch/distributed): when this process was
launched as part of a group (launch/env sets REPRO_COORDINATOR /
REPRO_NUM_PROCESSES / REPRO_PROCESS_ID), main() joins it via
jax.distributed BEFORE the first device query and every rank becomes one
data-parallel replica: its own local (data, model) mesh
(distributed.serving_mesh — never the global-device builder), its own
compiled chip stack (deterministic from the shared seed), and in
--traffic mode the deterministic request subset
distributed.route_requests assigns it from the ONE seeded stream. No jit
spans processes. Rank 0 owns the output files: per-rank summaries and
rank-tagged metrics gather through the coordinator KV store, and rank 0
writes the merged metrics/Prometheus/summary (obs.merge_registries —
per-rank series stay distinct under their rank label). The
one-decode-trace contract is asserted PER RANK before the gather.
Launch: python -m repro.launch.env --procs 2 --host-devices 2 -- \
    python -m repro.launch.serve --smoke --cim --traffic ...
"""
from __future__ import annotations

import argparse
import json
from typing import Any, List, NamedTuple

import jax
import jax.numpy as jnp

from .. import configs
from ..models import transformer as T
from ..data import lm_tokens
from ..obs import MetricsRegistry, TraceBuffer
from ..obs.chipmeter import ChipMeter
from ..obs.clock import stopwatch, timed_call
from .steps import arch_serving, make_decode_step


def _add_obs_flags(ap):
    ap.add_argument("--metrics-out", default="",
                    help="write the metrics registry as JSON at exit")
    ap.add_argument("--prom-out", default="",
                    help="write the metrics registry in Prometheus text "
                         "exposition format at exit")
    ap.add_argument("--trace-out", default="",
                    help="write per-request span timelines as Chrome "
                         "trace-event JSON (open in Perfetto) at exit")
    ap.add_argument("--summary-out", default="",
                    help="write the run's summary stats as JSON")
    ap.add_argument("--strict-jit", action="store_true",
                    help="turn the one-trace-per-plan contract into a hard "
                         "assertion: any steady-state retrace raises")


def _write_obs(args, metrics, trace=None, summary=None, extra_labels=None):
    """Flush whichever observability outputs were requested. `metrics`
    is a MetricsRegistry, or an already-merged `to_dict` document (the
    multi-rank path: rank 0 holds the fleet's series, no live registry
    exists for them)."""
    if isinstance(metrics, dict):
        from ..obs import dict_to_prometheus
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(metrics, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"metrics: wrote {args.metrics_out}")
        if args.prom_out:
            with open(args.prom_out, "w") as f:
                f.write(dict_to_prometheus(metrics))
            print(f"metrics: wrote {args.prom_out}")
    else:
        if args.metrics_out:
            metrics.write_json(args.metrics_out, extra_labels)
            print(f"metrics: wrote {args.metrics_out}")
        if args.prom_out:
            metrics.write_prometheus(args.prom_out, extra_labels)
            print(f"metrics: wrote {args.prom_out}")
    if args.trace_out and trace is not None:
        trace.write(args.trace_out)
        print(f"trace: wrote {args.trace_out} ({len(trace.events)} events)")
    if args.summary_out and summary is not None:
        with open(args.summary_out, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"summary: wrote {args.summary_out}")


class TrafficRun(NamedTuple):
    """What `main` returns in --traffic mode: the run's summary stats, the
    engine that served it (params, pool, compiled steps) and this rank's
    requests with their tokens — and logits rows under --capture-logits."""
    stats: dict
    engine: Any
    requests: List


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="serve only the first N layers of the config: a "
                         "depth cut, every width unchanged (0 = full depth)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--traffic", action="store_true",
                    help="continuous-batching mode: serve an open-loop "
                         "Poisson request stream through the slotted pool "
                         "(launch/scheduler) instead of one static batch")
    ap.add_argument("--requests", type=int, default=16,
                    help="--traffic: number of requests in the stream")
    ap.add_argument("--slots", type=int, default=0,
                    help="--traffic: pool slots (0 = --batch)")
    ap.add_argument("--chunk", type=int, default=32,
                    help="--traffic: prefill chunk size (keep a multiple "
                         "of 32 so recurrent-arch chunked prefill stays "
                         "bitwise vs one-shot)")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="--traffic: Poisson arrival rate (req/s)")
    ap.add_argument("--capture-logits", action="store_true",
                    help="--traffic: keep every request's logits rows on "
                         "the host (one device-to-host copy per token; for "
                         "checks against a reference, not for timing)")
    ap.add_argument("--cim", action="store_true",
                    help="serve dense-block projections through the packed "
                         "CIM engine (programs the chip before serving)")
    ap.add_argument("--cim-mode", default="ideal",
                    choices=["ideal", "relaxed", "writeverify"],
                    help="conductance programming fidelity for --cim")
    ap.add_argument("--cim-bits", type=int, default=0,
                    help="bit-serial input precision for --cim (1..8, "
                         "paper Fig. 1d; 0 = keep the arch default). The "
                         "whole chip recompiles and serves at this "
                         "precision — latency/energy scale with it")
    ap.add_argument("--cim-ir-drop", type=float, default=0.0,
                    help="ir_drop_alpha for --cim: > 0 plans IR-drop-bounded "
                         "vertical column splits")
    ap.add_argument("--cim-cores", type=int, default=0,
                    help="cores per chip for --cim (0 = NeuRRAM's 48); "
                         "small values force merged-core scheduled plans")
    ap.add_argument("--cim-mesh", default="auto",
                    help="real-mesh TP execution for --cim: 'auto' builds "
                         "the serving Mesh over the local devices and runs "
                         "multi-shard dispatches under shard_map; 'off' "
                         "keeps the unrolled in-process shard loop; 'DxM' "
                         "(e.g. '1x8') forces a (data, model) shape")
    _add_obs_flags(ap)
    args = ap.parse_args(argv)
    from .env import enable_compile_cache
    enable_compile_cache()

    # join the process group (if any) BEFORE the first device query —
    # jax.distributed must initialize ahead of backend topology pinning
    from . import distributed as dist
    dist_on = dist.initialize()
    rank, n_ranks = dist.process_info()

    cfg = configs.get(args.arch, smoke=args.smoke)
    cfg = cfg.replace(dtype=jnp.float32 if args.smoke else cfg.dtype)
    if args.layers:
        if not 1 <= args.layers <= cfg.n_layers:
            ap.error(f"--layers must be in 1..{cfg.n_layers}, "
                     f"got {args.layers}")
        print(f"depth: serving {args.layers} of {cfg.n_layers} layers "
              f"(d_model={cfg.d_model}, d_ff={cfg.d_ff}, "
              f"vocab={cfg.vocab} unchanged)")
        cfg = cfg.replace(n_layers=args.layers)
    mesh = None
    if args.cim:
        cfg = cfg.replace(cim_mode="packed", dtype=jnp.float32,
                          cim_ir_drop=args.cim_ir_drop)
        if args.cim_bits:
            if not 1 <= args.cim_bits <= 8:
                ap.error(f"--cim-bits must be in 1..8, got {args.cim_bits}")
            # ONE source of truth: the arch config. deploy_cim and the
            # serving jits both derive their CIMConfig from it
            # (models/nn.arch_cim_config), so the chip is compiled AND
            # served at this precision.
            cfg = cfg.replace(cim_in_bits=args.cim_bits)
        if args.cim_mesh == "auto":
            if dist_on:
                # per-replica mesh over LOCAL devices: the global-device
                # builder would span processes and make the pool
                # non-addressable from the engine's host loop
                mesh = dist.serving_mesh()
            else:
                from .mesh import serving_mesh
                mesh = serving_mesh()
        elif args.cim_mesh != "off":
            import re
            from .mesh import make_mesh
            m_ = re.fullmatch(r"(\d+)x(\d+)", args.cim_mesh)
            if not m_:
                ap.error(f"--cim-mesh must be 'auto', 'off' or 'DxM' "
                         f"(e.g. '1x8'), got {args.cim_mesh!r}")
            shape = (int(m_.group(1)), int(m_.group(2)))
            mesh = make_mesh(shape, ("data", "model"),
                             devices=jax.local_devices())
        if mesh is not None:
            # the prefill/decode jits close over cfg — and so over the mesh
            cfg = cfg.replace(cim_mesh=mesh)
    key = jax.random.PRNGKey(0)
    sv = arch_serving(cfg)
    params = sv.init_params(key)
    deploy_s = 0.0
    if args.cim:
        from ..core.types import CoreSpec
        from .mesh import serving_mesh_shape
        # 'off' still derives the TP width from the local device count
        # (per-process under jax.distributed — device_count() would span
        # the whole group); with a real mesh the deploy derives it from
        # the mesh itself (models/nn._resolve_mesh) so width and
        # placement cannot disagree
        if mesh is not None:
            mesh_shape = None
        elif dist_on:
            from .mesh import mesh_shape_for
            mesh_shape = mesh_shape_for(len(jax.local_devices()))
        else:
            mesh_shape = serving_mesh_shape()
        spec = CoreSpec(n_cores=args.cim_cores) if args.cim_cores else None
        from ..core.verify import verify_deployed
        with stopwatch() as sw:
            params = verify_deployed(sv.deploy_cim(
                jax.random.PRNGKey(7), params, mode=args.cim_mode,
                mesh_shape=mesh_shape, spec=spec))
            jax.block_until_ready(params)
        deploy_s = sw.s
        tp = (dict(mesh.shape)["model"] if mesh is not None
              else mesh_shape.get("model", 1))
        n_packed = sum(1 for k in params["layers"] if k.endswith("_cim"))
        n_shared = sum(1 for k in params.get("shared_attn", {})
                       if k.endswith("_cim"))
        shared = (f" + {n_shared} shared-attn projections"
                  if n_shared else "")
        exec_mode = ("shard_map" if mesh is not None and tp > 1
                     else "unrolled")
        rtag = f"[rank {rank}/{n_ranks}] " if dist_on else ""
        print(f"{rtag}cim: compiled {n_packed} projection stacks "
              f"x {cfg.n_layers} layers{shared} ({args.cim_mode}, "
              f"bits={cfg.cim_in_bits}/{cfg.cim_out_bits}, "
              f"tp={tp}, exec={exec_mode}) "
              f"in {sw.s:.1f}s")
    if args.traffic:
        return _serve_traffic(args, cfg, params, mesh, deploy_s=deploy_s,
                              rank=rank, n_ranks=n_ranks)

    max_len = args.prompt_len + args.gen + (cfg.vis_patches or 0)
    cache = sv.init_state(args.batch, max_len)
    prompts = lm_tokens(jax.random.PRNGKey(1), args.batch, args.prompt_len,
                        cfg.vocab)
    memory = None
    if cfg.enc_layers > 0:
        src = 0.02 * jax.random.normal(jax.random.PRNGKey(2),
                                       (args.batch, args.prompt_len,
                                        cfg.d_model), cfg.dtype)
        memory = T._encode(params, src, cfg)

    # On a mesh, pin the cache output to the canonical cache_pspecs
    # NamedShardings (the scheduler pins pool_pspecs the same way):
    # unpinned, GSPMD returns fresh sharding objects each call and the C++
    # pjit call cache misses on every decode step.
    ns = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        from ..distributed.sharding import cache_pspecs, fit_pspecs
        ns = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            fit_pspecs(cache, cache_pspecs(cache, data_axes=("data",)),
                       mesh))
    pin = {"out_shardings": (None, ns)} if ns is not None else {}
    prefill = jax.jit(sv.prefill, **pin)
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,), **pin)

    # timed_call (repro.obs.clock, re-exported by benchmarks/_timing):
    # block_until_ready around the step. The first prefill/decode dispatch
    # carries compile time, so per-token stats start at the second decode
    # step (warmup excluded).
    metrics = MetricsRegistry()
    meter = ChipMeter.from_params(params, cfg.cim_in_bits, cfg.cim_out_bits)
    h_dec = metrics.histogram("static_decode_step_s",
                              "static decode step seconds")
    (logits, cache), t_prefill = timed_call(prefill, params, cache, prompts,
                                            memory)
    metrics.histogram("static_prefill_s",
                      "static batch prefill seconds").observe(t_prefill)
    meter.count_rows(args.batch * args.prompt_len)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    generated = [tok]
    step_lat = []
    for i in range(args.gen - 1):
        batch = {"tokens": tok}
        if memory is not None:
            batch["memory"] = memory
        (logits, cache), dt = timed_call(decode, params, cache, batch)
        meter.count_rows(args.batch)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        generated.append(tok)
        if i > 0:                       # step 0 compiles the decode jit
            step_lat.append(dt)
            h_dec.observe(dt)
    t_decode = (sum(step_lat) / len(step_lat)) if step_lat else 0.0
    out = jnp.concatenate(generated, axis=1)
    tag = " cim=packed" if args.cim else ""
    if dist_on:
        tag += f" rank={rank}/{n_ranks}"
    thr = (args.batch / t_decode) if t_decode else float("nan")
    print(f"arch={cfg.name}{tag} batch={args.batch} "
          f"prefill={t_prefill*1e3:.1f}ms "
          f"decode={t_decode*1e3:.1f}ms/tok "
          f"throughput={thr:.1f} tok/s")
    print("sample token ids:", out[0, :16].tolist())
    meter.export(metrics)
    n_tok = args.batch * args.gen
    energy_pj = meter.energy_pj()
    summary = {
        "mode": "static",
        "arch": cfg.name,
        "cim": bool(args.cim),
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "gen": args.gen,
        "tokens": n_tok,
        "prefill_ms": t_prefill * 1e3,
        "decode_ms_per_tok": t_decode * 1e3,
        "tok_per_s": (args.batch / t_decode) if t_decode else 0.0,
        "mvm_dispatches": meter.mvm_dispatches(),
        "energy_pj": energy_pj,
        "pj_per_token": energy_pj / n_tok if n_tok else 0.0,
        "sample_tokens": out[0, :16].tolist(),
    }
    if dist_on:
        # static mode replicates the identical batch per rank (a group
        # smoke, not a routed workload); rank 0 owns the output files
        summary.update({"rank": rank, "ranks": n_ranks})
        if rank == 0:
            _write_obs(args, metrics, summary=summary,
                       extra_labels={"rank": str(rank)})
    else:
        _write_obs(args, metrics, summary=summary)
    return out


def _serve_traffic(args, cfg, params, mesh=None, deploy_s=0.0, rank=0,
                   n_ranks=1):
    """Continuous-batching mode: open-loop Poisson traffic through the
    slotted pool (launch/scheduler.ContinuousBatchingEngine). On a real
    mesh the pool itself is placed per distributed/sharding.pool_pspecs
    (slot dim over 'data') so every engine jit sees stable shardings —
    required for the one-decode-trace contract.

    Multi-process (n_ranks > 1): the SAME seeded stream is built on
    every rank and distributed.route_requests carves out this replica's
    share; the one-decode-trace contract is asserted per rank; rank 0
    gathers every rank's summary + rank-tagged metrics over the
    coordinator KV store and writes the merged outputs."""
    import numpy as np
    from ..data import traffic_requests
    from .scheduler import ContinuousBatchingEngine, Request

    if cfg.enc_layers > 0 or cfg.vis_patches > 0:
        raise SystemExit("--traffic serves decoder-only archs (enc-dec / "
                         "vlm prefixes need per-slot memory plumbing)")
    slots = args.slots or args.batch
    page = args.chunk
    min_len = page
    max_prompt = max(args.prompt_len - args.prompt_len % page, page)
    gen_hi = max(args.gen, 2)
    tr = traffic_requests(jax.random.PRNGKey(1), args.requests, cfg.vocab,
                          min_len=min_len, max_len=max_prompt, page=page,
                          rate=args.rate, min_gen=max(args.gen // 2, 1),
                          max_gen=gen_hi)
    max_len = max_prompt + gen_hi
    toks = np.asarray(tr.tokens)
    lens = np.asarray(tr.lengths)
    reqs = [Request(rid=i, prompt=toks[i, :lens[i]],
                    max_new=int(tr.gen[i]), arrival=float(tr.arrivals[i]))
            for i in range(args.requests)]
    dist_on = n_ranks > 1
    if dist_on:
        from .distributed import route_requests
        reqs = route_requests(reqs, n_ranks, rank)
    metrics = MetricsRegistry()
    trace = TraceBuffer() if args.trace_out else None
    eng = ContinuousBatchingEngine(cfg, params, n_slots=slots,
                                   max_len=max_len, chunk=args.chunk,
                                   mesh=mesh, metrics=metrics, trace=trace,
                                   strict_jit=args.strict_jit,
                                   capture_logits=args.capture_logits)
    stats = eng.run(reqs)
    stats["deploy_s"] = deploy_s
    # per-rank, BEFORE any gather: a retracing replica must fail its own
    # process, not hide inside the fleet aggregate
    assert stats["decode_traces"] == 1, \
        f"decode retraced across occupancy changes: {stats['decode_traces']}"
    tag = " cim=packed" if args.cim else ""
    rtag = f"[rank {rank}/{n_ranks}] " if dist_on else ""
    print(f"{rtag}arch={cfg.name}{tag} traffic: {stats['requests']} reqs "
          f"slots={slots} chunk={args.chunk} rate={args.rate}/s -> "
          f"{stats['tokens']} tokens in {stats['wall_s']:.2f}s "
          f"({stats['tok_per_s']:.1f} tok/s) "
          f"p50={stats['p50_ms']:.1f}ms p99={stats['p99_ms']:.1f}ms "
          f"ttft_p50={stats['ttft_p50_ms']:.1f}ms "
          f"decode_traces={stats['decode_traces']}")
    if stats["energy_pj"] > 0:
        print(f"{rtag}chip energy: {stats['energy_pj']/1e6:.2f} uJ "
              f"({stats['pj_per_token']/1e3:.1f} nJ/token, "
              f"{stats['tops_per_w']:.2f} TOPS/W, "
              f"utilization={stats['utilization']:.2f})")
    summary = dict(stats)
    summary.update({"mode": "traffic", "arch": cfg.name,
                    "cim": bool(args.cim), "slots": slots,
                    "chunk": args.chunk, "rate": args.rate})
    if not dist_on:
        _write_obs(args, metrics, trace=trace, summary=summary)
        return TrafficRun(stats, eng, reqs)

    # ---- rank-0 reporting contract: gather, merge, write once
    from ..obs import merge_registries
    from .distributed import gather_json, global_mesh_shape, merge_summaries
    summary.update({"rank": rank, "ranks": n_ranks})
    docs = gather_json("serve_traffic", {
        "summary": summary,
        "metrics": metrics.to_dict(extra_labels={"rank": str(rank)})})
    if rank != 0:
        return TrafficRun(stats, eng, reqs)
    merged = merge_summaries([d["summary"] for d in docs])
    merged.update({"mode": "traffic", "arch": cfg.name,
                   "cim": bool(args.cim), "slots": slots,
                   "chunk": args.chunk, "rate": args.rate,
                   "mesh_shape": global_mesh_shape(),
                   "routing": "round_robin"})
    print(f"fleet[{n_ranks} replicas]: {merged['requests']} reqs -> "
          f"{merged['tokens']} tokens, aggregate "
          f"{merged['tok_per_s']:.1f} tok/s "
          f"(slowest replica wall {merged['wall_s']:.2f}s), "
          f"p99={merged['p99_ms']:.1f}ms, "
          f"decode_traces(max)={merged['decode_traces']}")
    _write_obs(args, merge_registries([d["metrics"] for d in docs]),
               trace=trace, summary=merged)
    return TrafficRun(stats, eng, reqs)


if __name__ == "__main__":
    main()

"""Open-loop traffic harness: continuous batching vs the static batch.

One seeded Poisson request stream (data/synthetic.traffic_requests — mixed
prompt lengths quantized to the prefill page, per-request generation
budgets, exponential inter-arrivals) is served twice through the SAME
params:

  * continuous_<arch>: launch/scheduler.ContinuousBatchingEngine — slotted
    KV/state pool, admission/eviction between decode steps, chunked prefill
    interleaved with decode. Open loop: requests arrive on schedule whether
    or not the engine keeps up.
  * static_<arch>: launch/scheduler.serve_static — today's serve.py loop at
    equal request load: fixed batches in arrival order (each batch waits
    for its last member to arrive), prompts padded to the group max, one
    prefill, lockstep decode to the group's max generation budget.

Every step is timed through benchmarks/_timing.timed_call
(block_until_ready, warmup/compile excluded); rows report p50/p99 token
latency, TTFT and tokens/sec into BENCH_serving.json alongside
BENCH_mapping.json.

Two gates, split by determinism exactly like bench_mapping: the
one-trace-per-plan contract — the pool decode jit must compile ONCE across
all occupancy changes — always fails the run; the throughput gate —
continuous batching strictly beats the static batch on tokens/sec at equal
request load — is a warning by default (shared CI machines make wall-clock
gates flaky) and enforced under --enforce-timing.

Scale-out rows (--scaling, on by default): the same stream served by 1
vs 2 data-parallel replicas, each replica a subprocess child
(benchmarks/bench_serving_child.py) with its own engine + chip stack and
the launch/distributed.route_requests subset of the stream, closed-loop
(realtime=False). Fleet aggregate = total tokens / slowest replica wall
(replicas are independent, so fleet wall is the max). On hosts with
enough cores the 2-replica pair runs CONCURRENTLY as a real
jax.distributed group; on a one-core CI box the replicas run
sequentially as solo processes (concurrent ranks timesharing one core
would measure contention, not scaling) — the row's "mode" field records
which shape produced the number. The scaling gate — 2-replica aggregate
tokens/sec strictly above 1-replica — follows the same determinism
split: warning by default, enforced under --enforce-timing (the bench
tier).

CLI (the CI bench-smoke step):

    python -m benchmarks.bench_serving --quick --out BENCH_serving.json
"""
import argparse
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import traffic_requests
from repro.launch.scheduler import (ContinuousBatchingEngine, Request,
                                    serve_static)
from repro.launch.steps import arch_serving
from repro.obs import MetricsRegistry


def _requests(tr, n):
    toks, lens = np.asarray(tr.tokens), np.asarray(tr.lengths)
    return [Request(rid=i, prompt=toks[i, :lens[i]], max_new=int(tr.gen[i]),
                    arrival=float(tr.arrivals[i])) for i in range(n)]


def run(arch="gemma2-9b", *, quick=False, cim=False, n_requests=None,
        slots=4, chunk=32, rate=100.0, seed=1):
    cfg = configs.get(arch, smoke=True).replace(dtype=jnp.float32)
    if cim:
        cfg = cfg.replace(cim_mode="packed")
    sv = arch_serving(cfg)
    params = sv.init_params(jax.random.PRNGKey(0))
    if cim:
        params = sv.deploy_cim(jax.random.PRNGKey(7), params, mode="ideal",
                               mesh_shape={"model": 1})
    n = n_requests or (12 if quick else 32)
    max_prompt, max_gen = (64, 8) if quick else (96, 16)
    tr = traffic_requests(jax.random.PRNGKey(seed), n, cfg.vocab,
                          min_len=chunk, max_len=max_prompt, page=chunk,
                          rate=rate, min_gen=2, max_gen=max_gen)
    max_len = max_prompt + max_gen

    # both paths record into ONE shared registry (repro.obs) — the same
    # families `serve --traffic --metrics-out` exports, so bench rows and
    # serving telemetry come from identical instruments
    metrics = MetricsRegistry()
    eng = ContinuousBatchingEngine(cfg, params, n_slots=slots,
                                   max_len=max_len, chunk=chunk,
                                   metrics=metrics)
    cont = eng.run(_requests(tr, n))

    # the static baseline serves the SAME stream; moe_dropless matches the
    # engine's forced setting so both paths run identical model math
    stat = serve_static(eng.cfg, params, _requests(tr, n), batch=slots,
                        max_len=max_len, metrics=metrics)

    rows = [
        (f"continuous_{arch}", cont["p50_ms"] * 1e3, {
            "p50_ms": cont["p50_ms"], "p99_ms": cont["p99_ms"],
            "ttft_p50_ms": cont["ttft_p50_ms"],
            "tok_per_s": cont["tok_per_s"], "tokens": cont["tokens"],
            "requests": cont["requests"], "wall_s": cont["wall_s"],
            "slots": slots, "chunk": chunk, "rate": rate,
            "decode_traces": cont["decode_traces"],
            "jit_traces_pool_decode": metrics.value(
                "jit_traces", entry="pool_decode"),
            "registry_tokens": int(
                metrics.value("serve_tokens_generated")),
            "mvm_dispatches": cont["mvm_dispatches"],
            "energy_pj": cont["energy_pj"],
            "pj_per_token": cont["pj_per_token"],
            "tops_per_w": cont["tops_per_w"],
            "utilization": cont["utilization"]}),
        (f"static_{arch}", stat["p50_ms"] * 1e3, {
            "p50_ms": stat["p50_ms"], "p99_ms": stat["p99_ms"],
            "tok_per_s": stat["tok_per_s"], "tokens": stat["tokens"],
            "requests": stat["requests"], "wall_s": stat["wall_s"],
            "batch": slots,
            "mvm_dispatches": stat["mvm_dispatches"],
            "energy_pj": stat["energy_pj"],
            "pj_per_token": stat["pj_per_token"],
            "utilization": stat["utilization"]}),
    ]
    return rows


def _replica_fleet(n_replicas, *, arch, cim, requests, slots, chunk,
                   max_prompt, max_gen, seed):
    """Serve the seeded stream with n_replicas child processes; returns
    (per-rank result dicts, mode string). Concurrent jax.distributed
    group when the host has cores to back every rank, sequential solo
    replicas otherwise (see module docstring)."""
    from repro.launch import env as lenv
    lenv.require_cpu_parent("the replica scaling rows")
    concurrent = n_replicas > 1 and \
        len(os.sched_getaffinity(0)) >= 2 * n_replicas
    coord = f"localhost:{lenv.free_port()}" if concurrent else ""
    base = [sys.executable, "-m", "benchmarks.bench_serving_child",
            "--arch", arch, "--replicas", str(n_replicas),
            "--requests", str(requests), "--slots", str(slots),
            "--chunk", str(chunk), "--max-prompt", str(max_prompt),
            "--max-gen", str(max_gen), "--seed", str(seed)]
    if cim:
        base.append("--cim")
    cmds = [base + ["--rank", str(r)]
            + (["--coordinator", coord] if concurrent else [])
            for r in range(n_replicas)]
    env = lenv.runtime_env()      # solo env: strips any group vars
    if concurrent:
        procs = [subprocess.Popen(c, env=env, stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, text=True)
                 for c in cmds]
        results = [(p.communicate(), p.returncode) for p in procs]
        results = [subprocess.CompletedProcess(cmds[i], rc, out, err)
                   for i, ((out, err), rc) in enumerate(results)]
    else:
        results = [subprocess.run(c, env=env, capture_output=True,
                                  text=True) for c in cmds]
    per_rank = []
    for r, res in enumerate(results):
        if res.returncode != 0:
            raise SystemExit(f"scaling replica {r}/{n_replicas} failed "
                             f"(rc={res.returncode}):\n{res.stderr}")
        per_rank.append(json.loads(res.stdout.strip().splitlines()[-1]))
    mode = "grouped_concurrent" if concurrent else \
        ("solo" if n_replicas == 1 else "solo_sequential")
    return per_rank, mode


def run_scaling(arch="gemma2-9b", *, quick=False, cim=False, slots=2,
                chunk=32, seed=1):
    """1-replica vs 2-replica rows over one stream; aggregate tok/s =
    total tokens / slowest replica wall."""
    n = 10 if quick else 24
    max_prompt, max_gen = (64, 6) if quick else (96, 12)
    rows = []
    for n_replicas in (1, 2):
        per, mode = _replica_fleet(n_replicas, arch=arch, cim=cim,
                                   requests=n, slots=slots, chunk=chunk,
                                   max_prompt=max_prompt, max_gen=max_gen,
                                   seed=seed)
        tokens = sum(p["tokens"] for p in per)
        wall = max(p["wall_s"] for p in per)
        rows.append((f"serve_scaling_r{n_replicas}_{arch}", wall * 1e6, {
            "replicas": n_replicas, "mode": mode,
            "requests": sum(p["requests"] for p in per),
            "tokens": tokens, "wall_s": wall,
            "tok_per_s": tokens / wall if wall else 0.0,
            "decode_traces": max(p["decode_traces"] for p in per),
            "per_rank": per}))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke sizes (fewer/shorter requests)")
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--cim", action="store_true",
                    help="serve through the packed CIM chip stack")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--rate", type=float, default=100.0)
    ap.add_argument("--out", default="",
                    help="write rows as JSON (perf trajectory seed)")
    ap.add_argument("--no-scaling", action="store_true",
                    help="skip the 1-vs-2 data-parallel replica rows "
                         "(subprocess children; see module docstring)")
    ap.add_argument("--enforce-timing", action="store_true",
                    help="fail (not just warn) when continuous batching "
                         "does not beat the static batch on tokens/sec, "
                         "or 2 replicas do not beat 1 on aggregate "
                         "tokens/sec — for the dedicated bench job, not "
                         "the shared fast tier where wall-clock gates "
                         "flake")
    args = ap.parse_args(argv)
    rows = run(args.arch, quick=args.quick, cim=args.cim, slots=args.slots,
               chunk=args.chunk, rate=args.rate)
    if not args.no_scaling:
        rows += run_scaling(args.arch, quick=args.quick, cim=args.cim,
                            slots=args.slots, chunk=args.chunk)
    print("name,us_per_call,derived")
    for name, us, d in rows:
        print(f"{name},{us:.1f},{json.dumps(d, sort_keys=True)}")
    if args.out:
        payload = {name: {"us_per_call": us, **d} for name, us, d in rows}
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.out}")
    by = {name: d for name, _, d in rows}
    # deterministic contract (always enforced): ONE decode trace across
    # every admission/eviction/occupancy pattern of the run — per rank
    # on the scaling rows (each child also asserts its own)
    for name, d in by.items():
        if (name.startswith("continuous_") or
                name.startswith("serve_scaling_")) and \
                d["decode_traces"] != 1:
            raise SystemExit(f"pool decode trace contract broken on {name}: "
                             f"{d['decode_traces']} traces (expected 1)")
    # scaling gate: 2-replica aggregate tok/s strictly above 1-replica
    # (warning unless --enforce-timing, like every wall-clock gate)
    for name, d in by.items():
        if not (name.startswith("serve_scaling_r2_")):
            continue
        r1 = by.get(name.replace("_r2_", "_r1_"))
        if r1 is not None and not d["tok_per_s"] > r1["tok_per_s"]:
            msg = (f"2-replica scale-out did not beat 1 replica on {name}: "
                   f"{d['tok_per_s']:.1f} vs {r1['tok_per_s']:.1f} tok/s "
                   f"(mode={d['mode']})")
            if args.enforce_timing:
                raise SystemExit(msg)
            print(f"WARNING: {msg}")
    # throughput gate: continuous beats static at equal request load
    # (warning unless --enforce-timing)
    for name, d in by.items():
        if not name.startswith("continuous_"):
            continue
        sd = by.get(name.replace("continuous_", "static_"))
        if sd is not None and not d["tok_per_s"] > sd["tok_per_s"]:
            msg = (f"continuous batching did not beat static on {name}: "
                   f"{d['tok_per_s']:.1f} vs {sd['tok_per_s']:.1f} tok/s")
            if args.enforce_timing:
                raise SystemExit(msg)
            print(f"WARNING: {msg}")
    return rows


if __name__ == "__main__":
    main()

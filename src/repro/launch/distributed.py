"""Multi-host scale-out: data-parallel replicas of whole compiled chips.

NeuRRAM's path to heavy traffic is not one bigger chip but many
replicated ones — the multi-core TNSA already time-shares 48 cores, and
serving millions of users means replicating whole compiled chip stacks
the same way. This module is that replication layer:

  * `initialize` wraps `jax.distributed.initialize`, reading the
    REPRO_* coordination vars `launch/env.runtime_env` sets, so any
    entry point (serve, benches, test children) joins the process group
    by just being launched through `launch/env.launch`.
  * `serving_mesh` is the process-count-aware mesh builder: each process
    gets a (data, model) Mesh over its OWN local devices (the
    `launch/mesh.mesh_shape_for` factoring rule applied to the local
    device count). The logical cross-process serving mesh is
    (process_count * local_data) x model — `global_mesh_shape` — but no
    jit ever spans processes: replication over the cross-process 'data'
    axis is realized as one independent engine per process, each holding
    its own device-resident chip-stack shards. That keeps every array
    fully addressable (the engine's host-side admission loop reads pool
    state with np.asarray) and puts zero collectives on the serving
    path — replicas scale by not talking to each other.
  * `route_requests` is the admission router: one seeded request stream
    is generated identically on every rank (same PRNG key), and each
    rank serves the deterministic subset the policy assigns it —
    round-robin by rid (the default: balanced within every window of
    n_replicas requests) or a multiplicative rid hash (stateless sticky
    routing, the shape a front-end load balancer would use).
  * `merge_summaries` + the KV-store gather (`gather_json`) implement
    the rank-0 reporting contract: every rank publishes its summary and
    rank-tagged metrics through the coordinator's key-value store, rank
    0 merges and writes the single set of output files. Per-rank
    invariants (the one-decode-trace contract) are asserted per rank
    BEFORE the gather, so a broken replica fails its own process rather
    than hiding in an aggregate.

Single-process behavior: `initialize` is a no-op returning False, and
everything else degrades to the one-replica case — serve/bench code
calls these helpers unconditionally.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence, Tuple

from . import env as _env
from .mesh import make_mesh, mesh_shape_for

_INITIALIZED = False


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Join the process group if this rank belongs to one. Explicit args
    win; otherwise the REPRO_* env vars (launch/env) decide. Returns
    True iff a multi-process group is active afterwards. Must run before
    the first jax device query (backend init pins the topology), so
    entry points call it right after argument parsing."""
    global _INITIALIZED
    if num_processes is None:
        spec = _env.from_env()
        if spec is None:
            return _INITIALIZED
        coordinator, num_processes, process_id = spec
    if num_processes <= 1:
        return False
    if _INITIALIZED:
        return True
    import jax
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    _INITIALIZED = True
    return True


def process_info() -> Tuple[int, int]:
    """(rank, process_count) — (0, 1) outside any group."""
    import jax
    if not _INITIALIZED:
        return 0, 1
    return jax.process_index(), jax.process_count()


def serving_mesh(max_model: int = 16):
    """This process's replica Mesh: ('data', 'model') over the LOCAL
    devices, factored by `launch/mesh.mesh_shape_for`. Under
    `jax.distributed` the global-device builder
    (`launch/mesh.serving_mesh`) would span processes and make the
    engine's pool shards non-addressable from the host loop; this one
    never does. The 'data' axis here is the within-process slot stripe
    (`distributed/sharding.pool_pspecs`); the cross-process data axis is
    process replication (see `global_mesh_shape`)."""
    import jax
    local = jax.local_devices()
    shape = mesh_shape_for(len(local), max_model)
    return make_mesh((shape["data"], shape["model"]), ("data", "model"),
                     devices=local)


def global_mesh_shape(max_model: int = 16) -> Dict[str, int]:
    """The logical DxM shape of the whole serving fleet:
    {'data': process_count * local_data, 'model': local_model} — what
    the rank-0 summary reports as the deployment's replication width."""
    import jax
    local = mesh_shape_for(len(jax.local_devices()), max_model)
    _, n_proc = process_info()
    return {"data": n_proc * local["data"], "model": local["model"]}


# ----------------------------------------------------------- routing

def _rid_hash(rid: int) -> int:
    # Knuth multiplicative hash: stateless, stable across runs/ranks
    return (int(rid) * 2654435761) & 0xFFFFFFFF


def route_requests(requests: Sequence, n_replicas: int, replica: int,
                   policy: str = "round_robin") -> list:
    """The deterministic subset of `requests` this replica serves.
    Every rank evaluates this over the SAME full stream (identical
    seeds), so the subsets partition the stream exactly — no handoff
    protocol, no shared queue. Requests keep their arrival times: the
    open-loop schedule is a property of the stream, not the router."""
    if n_replicas < 1 or not 0 <= replica < n_replicas:
        raise ValueError(f"replica {replica} outside [0, {n_replicas})")
    if n_replicas == 1:
        return list(requests)
    if policy == "round_robin":
        return [r for r in requests if r.rid % n_replicas == replica]
    if policy == "hash":
        return [r for r in requests
                if _rid_hash(r.rid) % n_replicas == replica]
    raise ValueError(f"unknown routing policy {policy!r} "
                     "(round_robin | hash)")


# ------------------------------------------------- rank-0 aggregation

def merge_summaries(summaries: Sequence[dict]) -> dict:
    """One fleet summary from per-rank engine summaries
    (launch/scheduler.ContinuousBatchingEngine.run stats dicts).

    Exact aggregates: requests/tokens/energy/dispatches sum; wall is the
    slowest rank (replicas run concurrently, so fleet wall = max);
    tok_per_s = total tokens / that wall; pj_per_token = total energy /
    total tokens. Latency quantiles cannot be merged exactly from
    quantiles, so p50/TTFT are token-weighted means (reported as such)
    and p99 is the worst rank — the conservative tail. decode_traces is
    the max across ranks so the ==1 contract reads the same on the
    merged dict; the full per-rank breakdown rides along."""
    if not summaries:
        raise ValueError("merge_summaries needs at least one summary")
    tokens = sum(s["tokens"] for s in summaries)
    energy = sum(s.get("energy_pj", 0.0) for s in summaries)
    mvms = sum(s.get("mvm_dispatches", 0) for s in summaries)
    wall = max(s["wall_s"] for s in summaries)

    def _wmean(key):
        num = sum(s[key] * s["tokens"] for s in summaries)
        return num / tokens if tokens else 0.0

    util = (sum(s.get("utilization", 0.0) * s.get("mvm_dispatches", 0)
                for s in summaries) / mvms) if mvms else 0.0
    tops = (sum(s.get("tops_per_w", 0.0) * s.get("energy_pj", 0.0)
                for s in summaries) / energy) if energy else 0.0
    return {
        "ranks": len(summaries),
        "requests": sum(s["requests"] for s in summaries),
        "tokens": tokens,
        "wall_s": wall,
        "tok_per_s": tokens / wall if wall else 0.0,
        "p50_ms": _wmean("p50_ms"),
        "p99_ms": max(s["p99_ms"] for s in summaries),
        "ttft_p50_ms": _wmean("ttft_p50_ms"),
        "decode_traces": max(s["decode_traces"] for s in summaries),
        "mvm_dispatches": mvms,
        "energy_pj": energy,
        "pj_per_token": energy / tokens if tokens else 0.0,
        "tops_per_w": tops,
        "utilization": util,
        "per_rank": [{k: s[k] for k in
                      ("requests", "tokens", "wall_s", "tok_per_s",
                       "p50_ms", "p99_ms", "ttft_p50_ms",
                       "decode_traces") if k in s}
                     for s in summaries],
    }


# --------------------------------------------- coordinator KV plumbing

def _kv_client():
    """The process group's key-value store (the same service backing
    `jax.distributed.initialize` barriers). jax exposes it only under
    jax._src; pinning it here keeps the private import to ONE site."""
    from jax._src import distributed as _jd
    client = _jd.global_state.client
    if client is None:
        raise RuntimeError("no distributed client — initialize() first")
    return client


def gather_json(tag: str, payload: dict, timeout_s: float = 300.0
                ) -> Optional[List[dict]]:
    """All-ranks -> rank 0 gather of one JSON document per rank through
    the coordinator KV store. Every rank calls this with its payload;
    rank 0 returns the rank-ordered list, everyone else returns None
    (the rank-0 reporting contract: only rank 0 touches output files).
    `tag` namespaces the keys — use a distinct tag per gather point."""
    rank, n_proc = process_info()
    if n_proc == 1:
        return [payload] if rank == 0 else None
    client = _kv_client()
    timeout_ms = int(timeout_s * 1000)
    client.key_value_set(f"repro/{tag}/{rank}", json.dumps(payload))
    if rank != 0:
        return None
    return [json.loads(client.blocking_key_value_get(
        f"repro/{tag}/{r}", timeout_ms)) for r in range(n_proc)]

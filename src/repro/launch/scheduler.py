"""Continuous-batching serving engine: a slotted KV/state pool + the
request scheduler that drives it.

The paper's chip stacks are weight-stationary — one compiled chip serves
every in-flight request — so request-level serving is purely a cache and
scheduling layer over `launch/steps.arch_serving`:

  * Slot pool (`init_pool`): the batch dimension of the arch's native
    cache/state pytree becomes a pool of request slots. Per-slot sequence
    state covers dense KV caches AND the recurrent archs' S/h state (rwkv6 /
    mamba2 / zamba2 hybrid KV) uniformly, because every cache leaf keeps the
    slot dim at axis 1. The free-slot bitmap (`active`), each slot's last
    token (`tok`) and per-slot fill length (`len`, widened from the static
    path's scalar) live INSIDE the donated pool pytree as arrays — admission
    and eviction mutate values, never pytree structure, so the decode jit
    traces exactly ONCE across all occupancy changes.
  * Admission / eviction: between decode steps the host assigns free slots
    to arrived requests (FIFO, lowest slot first, never double-assigned),
    resets the slot's state to zeros, and chunk-prefills the prompt into it;
    a finished request just flips its `active` bit off — the slot is
    immediately reusable because admission resets it.
  * Chunked prefill interleaved with decode: prompts are split into
    `chunk`-sized pieces (default 32 — aligned with the recurrent archs'
    internal scan chunk, see below) and at most ONE chunk runs per engine
    iteration, so a long prompt never stalls in-flight decodes by more than
    one chunk's latency. The chunk engine is the arch's EXISTING chunked
    prefill (PR 3), run on a single-slot view of the pool
    (steps.make_slot_prefill_step).

Correctness contract (enforced by tests/test_scheduler.py): a request
served through the slotted pool is BITWISE-equal — logits, CIM ADC-count
path included — to the same request served alone through the static
serve.py path, for dense, MoE and recurrent archs. Three properties make
that hold:

  * packed CIM quantization uses static per-layer PACT alphas, and every
    per-row computation (matmul rows, softmax, norms) is independent of
    which other slots are occupied;
  * MoE dispatch must be DROPLESS (cfg.moe_dropless, forced on by this
    engine): with finite expert capacity a token's output depends on which
    other tokens compete for capacity — co-batched requests would perturb
    each other;
  * recurrent chunked-scan state (rwkv6 chunk=32, mamba2 chunk=64) is only
    reassociation-free when prefill chunk boundaries align with the
    internal scan chunk — hence chunk defaults to 32 and the traffic
    generator quantizes prompt lengths to a page multiple.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np

from ..kernels.cim_mvm import ops as cim_ops
from ..obs import MetricsRegistry, TraceBuffer
from ..obs.chipmeter import ChipMeter
from ..obs.clock import now as clock_now
from ..obs.clock import timed_call
from ..obs.jitwatch import JitWatcher
from ..obs.trace import ENGINE_PID, REQUEST_PID, Tracer
from .steps import (POOL_KEYS, arch_serving, make_pool_decode_step,
                    make_slot_prefill_step)


def packed_dispatches(jaxpr, weight: int = 1) -> Dict[str, int]:
    """The packed CIM dispatches one run of `jaxpr` makes, by how each
    reads its tiles: 'in_place' at a traced stack position (a layer of a
    scanned stack, core/mapping.split_tile_stacks) or 'sliced' at the
    constant position of a plan owning its tiles. A scan's body counts
    once per trip; the serving steps have no cond, whose branches would
    each count."""
    counts = {"in_place": 0, "sliced": 0}
    for eqn in jaxpr.eqns:
        if eqn.params.get("name") in cim_ops.PACKED_KERNELS:
            pos = eqn.invars[cim_ops.STACK_INDEX_ARG]
            counts["sliced" if isinstance(pos, jex_core.Literal)
                   else "in_place"] += weight
            continue
        trips = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    for read, n in packed_dispatches(
                            sub, weight * trips).items():
                        counts[read] += n
    return counts


def count_packed_dispatches(fn, gauge, entry: str):
    """`fn`, setting `gauge{entry, tile_read}` to its `packed_dispatches`
    whenever jit traces it. `fn` is traced once, to a jaxpr that is read
    and then evaluated in its place, so the count costs no second trace
    and nothing per call."""
    @functools.wraps(fn)
    def traced(*args):
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        for read, n in packed_dispatches(closed.jaxpr).items():
            gauge.set(n, entry=entry, tile_read=read)
        out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts,
                                  *jax.tree_util.tree_leaves(args))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(out_shape), out)
    return traced


def init_pool(cfg, n_slots: int, max_len: int, mesh=None):
    """Slot pool pytree: the arch's native cache with `len` widened to a
    per-slot (n_slots,) vector, plus the `active` bitmap and per-slot last
    token. With a mesh, leaves are placed per
    distributed/sharding.pool_pspecs (slot dim over the 'data' axis)."""
    sv = arch_serving(cfg)
    pool = dict(sv.init_state(n_slots, max_len))
    pool["len"] = jnp.zeros((n_slots,), jnp.int32)
    pool["active"] = jnp.zeros((n_slots,), bool)
    pool["tok"] = jnp.zeros((n_slots, 1), jnp.int32)
    if mesh is not None:
        from jax.sharding import NamedSharding
        from ..distributed.sharding import pool_pspecs
        specs = pool_pspecs(pool)
        pool = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            pool, specs)
    return pool


def _reset_slot(pool, slot):
    """Zero one slot's sequence state + bookkeeping (admission reset)."""
    out = {}
    for k, a in pool.items():
        if k in ("len", "active"):
            out[k] = a.at[slot].set(0 if k == "len" else False)
        elif k == "tok":
            out[k] = a.at[slot, 0].set(0)
        else:
            out[k] = a.at[:, slot].set(jnp.zeros((), a.dtype))
    return out


def _set_active(pool, slot, flag):
    return dict(pool, active=pool["active"].at[slot].set(flag))


@dataclasses.dataclass
class Request:
    """One serving request. `arrival` is seconds relative to run start
    (open-loop traffic); results are filled in by the engine."""
    rid: int
    prompt: np.ndarray                   # (L,) int32
    max_new: int
    arrival: float = 0.0
    # results
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_lat: List[float] = dataclasses.field(default_factory=list)
    t_first: float = -1.0                # arrival -> first token (TTFT)
    t_done: float = -1.0
    t_admit: float = -1.0                # seconds into the run at admission
    energy_pj: float = 0.0               # attributed modeled chip energy
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _PrefillJob:
    slot: int
    req: Request
    chunks: List[np.ndarray]
    next: int = 0


class ContinuousBatchingEngine:
    """Request-level continuous batching over one compiled chip stack.

    One decode trace serves every occupancy pattern; admission, eviction
    and chunked prefill are value-level updates on the donated pool.
    `capture_logits=True` records each request's per-token logits rows
    (numpy) — the bitwise pool-vs-static contract is asserted on these.
    """

    def __init__(self, cfg, params, n_slots: int, max_len: int, *,
                 chunk: int = 32, mesh=None, capture_logits: bool = False,
                 metrics: Optional[MetricsRegistry] = None,
                 trace: Optional[TraceBuffer] = None,
                 strict_jit: bool = False):
        if cfg.n_experts > 0 and not cfg.moe_dropless:
            # engine-owned contract: co-batched requests must not compete
            # for expert capacity (see module docstring)
            cfg = cfg.replace(moe_dropless=True)
        self.cfg = cfg
        # last gate before the pool jits close over the chip stacks: a
        # corrupt packed artifact (anything mutated between deploy and
        # engine init) fails HERE with a named invariant, not as a silent
        # wrong answer inside a dispatched kernel
        from ..core.verify import verify_deployed
        self.params = verify_deployed(params)
        self.n_slots = n_slots
        self.max_len = max_len
        self.chunk = chunk
        self.capture_logits = capture_logits
        self.pool = init_pool(cfg, n_slots, max_len, mesh=mesh)
        # On a mesh, pin every jit's pool output to the canonical
        # pool_pspecs NamedShardings. Without this GSPMD re-shards cache
        # leaves as it likes and returns fresh GSPMDSharding objects each
        # call — the C++ pjit call cache then misses every step (slow-path
        # dispatch) and the one-trace contract metric inflates with it.
        ns = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from ..distributed.sharding import pool_pspecs
            ns = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), pool_pspecs(self.pool))
        # Telemetry is always collected (one code path — metrics can't
        # perturb what they measure) into a private registry unless the
        # caller supplies a shared one; the trace buffer is opt-in. Phase
        # spans (obs/trace.Tracer) are always recorded: the profiler keeps
        # them only while it traces.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.trace = trace
        self.tracer = Tracer(self.metrics, trace)
        # Every engine jit goes through the watchdog: trace counts become a
        # metric on every run and, under strict_jit, a hard assertion; each
        # call is a serve.dispatch.<entry> span. The wrapper forwards calls
        # verbatim (same donation/shardings/static args), so compiled
        # semantics — and the bitwise pool-vs-static contract — are
        # untouched whether metrics are read or not.
        self.jitwatch = JitWatcher(strict=strict_jit, tracer=self.tracer)
        g_packed = self.metrics.gauge(
            "serve_packed_dispatches",
            "packed CIM dispatches per step call, by tile_read: in_place "
            "(indexing the layer stack) or sliced (a plan of its own)")
        self._decode = self.jitwatch.wrap(
            "pool_decode", count_packed_dispatches(
                make_pool_decode_step(cfg), g_packed, "pool_decode"),
            max_traces=1, donate_argnums=(1,),
            **({"out_shardings": (None, ns)} if ns is not None else {}))
        self._prefill = self.jitwatch.wrap(
            "slot_prefill", count_packed_dispatches(
                make_slot_prefill_step(cfg), g_packed, "slot_prefill"),
            donate_argnums=(1,),
            **({"out_shardings": (None, ns)} if ns is not None else {}))
        self._reset = self.jitwatch.wrap(
            "slot_reset", _reset_slot, max_traces=1, donate_argnums=(0,),
            **({"out_shardings": ns} if ns is not None else {}))
        self._activate = self.jitwatch.wrap(
            "slot_activate", _set_active, max_traces=2,  # static flag arg
            donate_argnums=(0,), static_argnums=(2,),
            **({"out_shardings": ns} if ns is not None else {}))
        self._free = list(range(n_slots))      # host mirror of ~active
        self._live: Dict[int, Request] = {}    # slot -> decoding request
        self._jobs: deque = deque()            # chunked prefills in flight
        self._rows_useful = 0                  # token rows that reached a req
        self._rows_dispatched = 0              # rows pushed through the chips
        self.chipmeter = ChipMeter.from_params(
            params, cfg.cim_in_bits, cfg.cim_out_bits)
        m = self.metrics
        self._m_admitted = m.counter(
            "serve_requests_admitted", "requests admitted to a slot")
        self._m_finished = m.counter(
            "serve_requests_finished", "requests fully served")
        self._m_chunks = m.counter(
            "serve_prefill_chunks", "prefill chunk dispatches")
        self._m_steps = m.counter(
            "serve_decode_steps", "pool decode step dispatches")
        self._m_tok_gen = m.counter(
            "serve_tokens_generated", "tokens emitted to requests")
        self._m_tok_pre = m.counter(
            "serve_tokens_prefilled", "prompt tokens prefilled")
        self._g_occ = m.gauge(
            "serve_slots_occupied", "live decoding slots (of n_slots)")
        self._g_queue = m.gauge(
            "serve_queue_depth", "requests waiting: arrived, no slot yet")
        self._h_decode = m.histogram(
            "serve_decode_step_s", "pool decode step wall seconds")
        self._h_chunk = m.histogram(
            "serve_prefill_chunk_s", "prefill chunk wall seconds")
        self._h_ttft = m.histogram(
            "serve_ttft_s", "arrival to first token, seconds")
        self._h_req = m.histogram(
            "serve_request_s", "arrival to last token, seconds")

    # ------------------------------------------------------------- plumbing

    def decode_traces(self) -> int:
        """Compiled-trace count of the pool decode step (contract: 1)."""
        return self._decode._cache_size()

    def decode_hlo(self) -> str:
        """Optimized HLO text of the pool decode step as compiled for the
        current params and pool — where a device check looks for its
        kernels (a persistent compilation cache makes this a cache hit)."""
        return self._decode.jitted.lower(self.params, self.pool) \
            .compile().as_text()

    def _chunks(self, prompt: np.ndarray) -> List[np.ndarray]:
        c = self.chunk
        return [prompt[i:i + c] for i in range(0, len(prompt), c)]

    def warmup(self, chunk_lens) -> None:
        """Compile the decode step and each distinct prefill-chunk length
        on the (empty) pool, then reset the scratch slot — keeps compile
        time out of every reported latency without a scratch pool."""
        for n in sorted(set(chunk_lens)):
            toks = jnp.zeros((1, int(n)), jnp.int32)
            _, self.pool = self._prefill(self.params, self.pool, toks,
                                         jnp.int32(0))
        # both static variants of the activate flag, so a sealed watcher
        # sees no fresh traces on the first real admit/evict
        self.pool = self._activate(self.pool, jnp.int32(0), True)
        self.pool = self._activate(self.pool, jnp.int32(0), False)
        self.pool = self._reset(self.pool, jnp.int32(0))
        _, self.pool = self._decode(self.params, self.pool)
        jax.block_until_ready(self.pool)

    # ------------------------------------------------------------ scheduling

    def _admit(self, req: Request) -> None:
        assert len(req.prompt) + req.max_new <= self.max_len, \
            f"request {req.rid} would overflow the slot (max_len)"
        with self.tracer.span("serve.admit", rid=req.rid):
            slot = self._free.pop(0)
            assert slot not in self._live, "slot double-assign"
            self.pool = self._reset(self.pool, jnp.int32(slot))
            self._jobs.append(_PrefillJob(slot, req,
                                          self._chunks(req.prompt)))
            self._m_admitted.inc()

    def _request_done(self, req: Request, slot: int) -> None:
        """Telemetry at a request's last token: latency histograms, its
        attributed chip energy (useful rows x per-token stack cost — the
        first generated token rides the final prefill chunk, so decode
        rows are len(tokens) - 1), and its trace span."""
        self._m_finished.inc()
        self._h_req.observe(req.t_done - req.arrival)
        rows = len(req.prompt) + max(len(req.tokens) - 1, 0)
        req.energy_pj = rows * self.chipmeter.per_token_pj()
        if self.trace is not None:
            t_admit = req.t_admit if req.t_admit >= 0 else req.arrival
            start = min(req.arrival, t_admit)
            self.trace.name_thread(REQUEST_PID, req.rid, f"req {req.rid}")
            self.trace.complete(
                "request", start, req.t_done - start,
                pid=REQUEST_PID, tid=req.rid,
                args={"rid": req.rid, "slot": slot,
                      "prompt_len": len(req.prompt),
                      "tokens": len(req.tokens),
                      "ttft_s": req.t_first,
                      "energy_pj": req.energy_pj})

    def _finish(self, slot: int, now: float) -> None:
        req = self._live.pop(slot)
        with self.tracer.span("serve.finish", rid=req.rid):
            req.t_done = now
            self.pool = self._activate(self.pool, jnp.int32(slot), False)
            self._free.append(slot)
            self._free.sort()
            self._request_done(req, slot)

    def _dispatch_and_wait(self, fn, wait: str, *args):
        """(outputs, seconds) of one step: the watched jit's dispatch (its
        serve.dispatch.<entry> span), then block_until_ready under the
        `wait` span. The seconds run to the wait's end and cover both, as
        the step histograms (serve_decode_step_s, serve_prefill_chunk_s)
        have always timed."""
        t0 = clock_now()
        out = fn(*args)
        with self.tracer.span(wait) as waited:
            jax.block_until_ready(out)
        return out, waited.t1 - t0

    def _prefill_one_chunk(self, now: float) -> float:
        """Run ONE chunk of the oldest in-flight prefill; returns step
        seconds. On the final chunk the slot goes live (its first token was
        seeded into pool['tok'] by the chunk step)."""
        job = self._jobs[0]
        chunk = job.chunks[job.next]
        with self.tracer.span("serve.prefill", rid=job.req.rid,
                              slot=job.slot, chunk=job.next + 1,
                              of=len(job.chunks)):
            toks = jnp.asarray(chunk[None], jnp.int32)
            (logits, self.pool), dt = self._dispatch_and_wait(
                self._prefill, "serve.prefill.wait", self.params, self.pool,
                toks, jnp.int32(job.slot))
            job.next += 1
            n_rows = len(chunk)
            self._m_chunks.inc()
            self._m_tok_pre.inc(n_rows)
            self._h_chunk.observe(dt)
            self.chipmeter.count_rows(n_rows)
            self._rows_useful += n_rows
            self._rows_dispatched += n_rows
            if self.trace is not None:
                self.trace.complete("prefill_chunk", now, dt, pid=REQUEST_PID,
                                    tid=job.req.rid,
                                    args={"slot": job.slot, "rid": job.req.rid,
                                          "rows": n_rows, "chunk": job.next,
                                          "of": len(job.chunks)})
            if job.next == len(job.chunks):
                self._jobs.popleft()
                req = job.req
                with self.tracer.span("serve.prefill.readback"):
                    first = int(np.argmax(np.asarray(logits[0])))
                    if self.capture_logits:
                        req.logits.append(np.asarray(logits[0]))
                req.tokens.append(first)
                req.token_lat.append(dt)
                self._m_tok_gen.inc()
                req.t_first = now + dt - req.arrival
                self._h_ttft.observe(req.t_first)
                if req.max_new == 1:
                    with self.tracer.span("serve.finish", rid=req.rid):
                        req.t_done = now + dt
                        self.pool = self._reset(self.pool, jnp.int32(job.slot))
                        self._free.append(job.slot)
                        self._free.sort()
                        self._request_done(req, job.slot)
                else:
                    self.pool = self._activate(self.pool, jnp.int32(job.slot),
                                               True)
                    self._live[job.slot] = req
            return dt

    def _decode_once(self, now: float) -> float:
        # Honest hardware accounting: the weight-stationary pool step
        # pushes ALL n_slots rows through every chip regardless of
        # occupancy — empty slots still cost energy. The useful/dispatched
        # ratio surfaces as the run's `utilization`.
        n_live = len(self._live)
        with self.tracer.span("serve.decode", live=n_live):
            (logits, self.pool), dt = self._dispatch_and_wait(
                self._decode, "serve.decode.wait", self.params, self.pool)
            self._m_steps.inc()
            self._m_tok_gen.inc(n_live)
            self._h_decode.observe(dt)
            self.chipmeter.count_rows(self.n_slots)
            self._rows_useful += n_live
            self._rows_dispatched += self.n_slots
            with self.tracer.span("serve.decode.readback"):
                toks = np.asarray(self.pool["tok"][:, 0])
            done = []
            with self.tracer.span("serve.decode.emit"):
                for slot, req in self._live.items():
                    req.tokens.append(int(toks[slot]))
                    req.token_lat.append(dt)
                    if self.capture_logits:
                        req.logits.append(np.asarray(logits[slot]))
                    if self.trace is not None:
                        self.trace.complete("decode", now, dt,
                                            pid=REQUEST_PID, tid=req.rid,
                                            args={"slot": slot})
                    if len(req.tokens) >= req.max_new:
                        done.append(slot)
            for slot in done:
                self._finish(slot, now + dt)
        return dt

    # -------------------------------------------------------------- serving

    def _schedule(self, pending: deque, t0: float, realtime: bool,
                  occ_last: tuple) -> tuple:
        """Top of a loop iteration: admit arrived requests to free slots,
        set the occupancy gauges, and write the occupancy counter when it
        changed. Returns the occupancy written last."""
        now = clock_now() - t0
        while pending and self._free and \
                (not realtime or pending[0].arrival <= now):
            pending[0].t_admit = now
            self._admit(pending.popleft())
        arrived = sum(r.arrival <= now for r in pending) \
            if realtime else len(pending)
        self._g_occ.set(len(self._live))
        self._g_queue.set(arrived + len(self._jobs))
        occ = (len(self._live), len(self._jobs), arrived)
        if self.trace is not None and occ != occ_last:
            self.trace.counter("occupancy", now, {
                "live_slots": occ[0], "prefilling": occ[1],
                "queued": occ[2]})
            return occ
        return occ_last

    def run(self, requests: List[Request], *, warm: bool = True,
            realtime: bool = True) -> Dict[str, Any]:
        """Open-loop serve: requests arrive at their `arrival` offsets
        whether or not the engine keeps up. Returns summary stats; per-token
        detail lands on each Request. With realtime=False arrival times are
        ignored (everything is admitted as soon as a slot frees up) — used
        by tests for deterministic scheduling."""
        if warm:
            self.warmup({c.shape[0] for r in requests
                         for c in self._chunks(r.prompt)})
            # warmup compiled every shape this run can produce — from here
            # on, any trace on any entry point is a contract violation
            self.jitwatch.seal()
        if self.trace is not None:
            self.trace.name_process(ENGINE_PID, "engine")
            self.trace.name_process(REQUEST_PID, "requests")
        pending = deque(sorted(requests, key=lambda r: (r.arrival, r.rid)))
        t0 = clock_now()
        self.tracer.origin = t0
        span = self.tracer.span
        occ_last = (-1, -1, -1)
        with self.tracer.gc_spans():
            while pending or self._jobs or self._live:
                with span("serve.iter"):
                    with span("serve.schedule"):
                        occ_last = self._schedule(pending, t0, realtime,
                                                  occ_last)
                    busy = False
                    # each step re-reads the clock: prefill and decode run
                    # sequentially within an iteration, and span starts
                    # must reflect the wall time the step actually began —
                    # stamping both with the top-of-loop `now` would
                    # overlap their spans (and let a slow prefill's span
                    # spill past a request that finished in the decode
                    # right after it)
                    if self._jobs:
                        self._prefill_one_chunk(clock_now() - t0)
                        busy = True
                    if self._live:
                        self._decode_once(clock_now() - t0)
                        busy = True
                    if not busy and pending and realtime:
                        # idle: nothing in flight, next request not yet
                        # arrived
                        wait = pending[0].arrival - (clock_now() - t0)
                        if wait > 0:
                            with span("serve.sleep"):
                                time.sleep(min(wait, 0.05))
        self.tracer.origin = None
        wall = clock_now() - t0
        self._g_occ.set(0)
        self._g_queue.set(0)
        self.chipmeter.export(self.metrics)
        self.jitwatch.export(self.metrics)
        lats = np.asarray([dt for r in requests for dt in r.token_lat])
        total = sum(len(r.tokens) for r in requests)
        energy_pj = self.chipmeter.energy_pj()
        return {
            "requests": len(requests),
            "tokens": total,
            "wall_s": wall,
            "tok_per_s": total / wall if wall > 0 else 0.0,
            "p50_ms": float(np.percentile(lats, 50) * 1e3) if total else 0.0,
            "p99_ms": float(np.percentile(lats, 99) * 1e3) if total else 0.0,
            "ttft_p50_ms": float(np.percentile(
                [r.t_first for r in requests], 50) * 1e3) if requests else 0.0,
            "decode_traces": self.decode_traces(),
            "mvm_dispatches": self.chipmeter.mvm_dispatches(),
            "energy_pj": energy_pj,
            "pj_per_token": energy_pj / total if total else 0.0,
            "tops_per_w": self.chipmeter.tops_per_w(),
            "utilization": (self._rows_useful / self._rows_dispatched
                            if self._rows_dispatched else 0.0),
        }


def serve_static(cfg, params, requests: List[Request], batch: int,
                 max_len: int, *, capture_logits: bool = False,
                 realtime: bool = True,
                 metrics: Optional[MetricsRegistry] = None) -> Dict[str, Any]:
    """The static-batch baseline at equal request load: requests are taken
    in arrival order, grouped into fixed batches of `batch`, prompts padded
    to the group max, prefilled once, then decoded in lockstep until every
    member hits its max_new (today's serve.py loop). Used by
    benchmarks/bench_serving.py as the tokens/sec comparison point.

    Metered with the same ChipMeter model as the engine, under static-path
    rules: prefill dispatches group_size x padded_len rows (left-padding is
    real dispatched work on a weight-stationary chip), decode dispatches
    group_size rows per lockstep step even for members already done — the
    padding + lockstep waste is exactly what `utilization` exposes against
    the continuous engine's number."""
    from .steps import make_decode_step
    sv = arch_serving(cfg)
    prefill = jax.jit(sv.prefill)
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
    meter = ChipMeter.from_params(params, cfg.cim_in_bits, cfg.cim_out_bits)
    m = metrics if metrics is not None else MetricsRegistry()
    h_pre = m.histogram("static_prefill_s", "static batch prefill seconds")
    h_dec = m.histogram("static_decode_step_s", "static decode step seconds")
    c_tok = m.counter("static_tokens", "tokens emitted by the static path")
    rows_useful = 0
    rows_dispatched = 0
    reqs = sorted(requests, key=lambda r: (r.arrival, r.rid))
    groups = [reqs[i:i + batch] for i in range(0, len(reqs), batch)]
    # warmup: compile each distinct (group size, padded prompt len) prefill
    # shape and the decode step before the clock starts — same treatment as
    # the continuous engine's warmup, so neither side pays compile time
    for gb, lp in sorted({(len(g), max(len(r.prompt) for r in g))
                          for g in groups}):
        cache = sv.init_state(gb, max_len)
        logits, cache = prefill(params, cache,
                                jnp.zeros((gb, lp), jnp.int32))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        jax.block_until_ready(decode(params, cache, tok))
    t0 = clock_now()
    for group in groups:
        if realtime:  # the whole batch must have arrived before it forms
            wait = max(r.arrival for r in group) - (clock_now() - t0)
            if wait > 0:
                time.sleep(wait)
        lp = max(len(r.prompt) for r in group)
        prompts = np.zeros((len(group), lp), np.int32)
        for j, r in enumerate(group):
            prompts[j, lp - len(r.prompt):] = r.prompt  # left-pad
        cache = sv.init_state(len(group), max_len)
        (logits, cache), dt = timed_call(prefill, params, cache,
                                         jnp.asarray(prompts))
        h_pre.observe(dt)
        meter.count_rows(len(group) * lp)
        rows_useful += sum(len(r.prompt) for r in group)
        rows_dispatched += len(group) * lp
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        now = clock_now() - t0
        for j, r in enumerate(group):
            r.tokens.append(int(tok[j, 0]))
            r.token_lat.append(dt)
            r.t_first = now - r.arrival
            c_tok.inc()
            if capture_logits:
                r.logits.append(np.asarray(logits[j]))
        gen_max = max(r.max_new for r in group)
        for _ in range(gen_max - 1):
            (logits, cache), dt = timed_call(decode, params, cache, tok)
            h_dec.observe(dt)
            meter.count_rows(len(group))
            rows_dispatched += len(group)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            now = clock_now() - t0
            for j, r in enumerate(group):
                if len(r.tokens) < r.max_new:  # lockstep: extras discarded
                    r.tokens.append(int(tok[j, 0]))
                    r.token_lat.append(dt)
                    rows_useful += 1
                    c_tok.inc()
                    if capture_logits:
                        r.logits.append(np.asarray(logits[j]))
        for r in group:
            r.t_done = clock_now() - t0
    wall = clock_now() - t0
    lats = np.asarray([dt for r in reqs for dt in r.token_lat])
    total = sum(len(r.tokens) for r in reqs)
    energy_pj = meter.energy_pj()
    return {
        "requests": len(reqs),
        "tokens": total,
        "wall_s": wall,
        "tok_per_s": total / wall if wall > 0 else 0.0,
        "p50_ms": float(np.percentile(lats, 50) * 1e3) if total else 0.0,
        "p99_ms": float(np.percentile(lats, 99) * 1e3) if total else 0.0,
        "mvm_dispatches": meter.mvm_dispatches(),
        "energy_pj": energy_pj,
        "pj_per_token": energy_pj / total if total else 0.0,
        "utilization": (rows_useful / rows_dispatched
                        if rows_dispatched else 0.0),
    }

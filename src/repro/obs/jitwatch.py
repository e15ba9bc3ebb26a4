"""Trace-count watchdogs for serving-path jits.

The bug class this guards at RUNTIME is the one PR 7 shipped and PR 8's
lint rule R001 catches statically: an engine jit whose `out_shardings`
are not pinned gets fresh GSPMDSharding objects per call, the C++ pjit
fast-path cache misses every step, and in the worst case the function
RETRACES — silently turning a microsecond dispatch into a multi-second
compile in the middle of serving. The engine's contract is ONE decode
trace across all occupancy changes; `JitWatcher` makes that contract an
exported metric (`jit_traces{entry=...}`) on every run and, opt-in, a
hard assertion (`strict=True` + `seal()` after warmup: any later trace
raises `JitRetraceError` naming the entry point).

Mechanics: `wrap(name, fun, **jit_kwargs)` jits `fun` with the EXACT
kwargs given (donation, shardings and static args are untouched — the
wrapper cannot change compiled semantics) and, after each call, reads the
jitted function's `_cache_size()`. That read is host-side bookkeeping on
an already-dispatched call — no device sync, no traced values. Compile
time is attributed by wall clock: a call that grew the cache carries its
(compile + dispatch) seconds into `compile_s`, which is exactly how the
engine's warmup accounting wants it (warmup absorbs the compile; steady
state must never grow the cache again). With a `Tracer`, every call is a
`serve.dispatch.<entry>` span: the enqueue of that jit, in one place for
every engine entry point.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax

from . import clock


class JitRetraceError(RuntimeError):
    """A sealed (or over-budget, under strict) entry point retraced."""


class WatchedJit:
    """A jitted callable plus its trace ledger. Drop-in: `__call__`
    forwards to the underlying jit; `_cache_size()` is preserved for
    callers that already count traces by hand."""

    def __init__(self, name: str, fun, *, max_traces: Optional[int],
                 watcher: "JitWatcher", **jit_kwargs):
        self.name = name
        self.jitted = jax.jit(fun, **jit_kwargs)
        self.max_traces = max_traces
        # jax.jit shares its compilation cache across wrappers of the SAME
        # function object (module-level step fns, unlike per-engine
        # closures), so a second engine in one process would inherit the
        # first one's entries — count traces relative to wrap time
        self._base = self.jitted._cache_size()
        self.traces = 0
        self.calls = 0
        self.compile_s = 0.0
        self._watcher = watcher
        self.span_name = f"serve.dispatch.{name}"
        functools.update_wrapper(self, fun,
                                 assigned=("__doc__", "__name__"),
                                 updated=())

    def __call__(self, *args, **kwargs):
        tracer = self._watcher.tracer
        if tracer is None:
            return self._call(args, kwargs)
        with tracer.span(self.span_name):
            return self._call(args, kwargs)

    def _call(self, args, kwargs):
        t0 = clock.now()
        out = self.jitted(*args, **kwargs)
        self.calls += 1
        n = self.jitted._cache_size() - self._base
        if n > self.traces:
            self.compile_s += clock.now() - t0
            self.traces = n
            w = self._watcher
            if w.sealed or (w.strict and self.over_budget):
                raise JitRetraceError(
                    f"jit entry point '{self.name}' traced (trace "
                    f"#{n}{', sealed after warmup' if w.sealed else ''}"
                    f"{'' if self.max_traces is None else f', budget {self.max_traces}'}) "
                    "— the one-trace-per-plan contract is broken: check "
                    "out_shardings pinning (lint R001) and that every "
                    "input shape/dtype was warmed")
        return out

    def _cache_size(self) -> int:
        return self.jitted._cache_size() - self._base

    @property
    def over_budget(self) -> bool:
        return self.max_traces is not None and self.traces > self.max_traces


class JitWatcher:
    """Trace ledger over a set of named entry points.

    strict=False (default): retraces are recorded and exported, never
    raised — the observability mode. strict=True: an entry exceeding its
    `max_traces` budget raises at the offending call. `seal()` (either
    mode) freezes the trace set — ANY later trace on any entry raises;
    the engine seals after warmup so steady-state serving is guaranteed
    compile-free. `tracer` (obs/trace.Tracer) makes each call a span.
    """

    def __init__(self, *, strict: bool = False, tracer=None):
        self.strict = strict
        self.tracer = tracer              # obs/trace.Tracer, or None
        self.sealed = False
        self.entries: Dict[str, WatchedJit] = {}

    def wrap(self, name: str, fun, *, max_traces: Optional[int] = None,
             **jit_kwargs) -> WatchedJit:
        if name in self.entries:
            raise ValueError(f"jit entry point {name!r} already wrapped")
        wj = WatchedJit(name, fun, max_traces=max_traces, watcher=self,
                        **jit_kwargs)
        self.entries[name] = wj
        return wj

    def seal(self) -> None:
        """Freeze the trace set: steady state must not compile."""
        self.sealed = True

    def check(self) -> None:
        """The opt-in hard assertion at a report boundary: raise if any
        entry point exceeded its trace budget during the run."""
        for wj in self.entries.values():
            if wj.over_budget:
                raise JitRetraceError(
                    f"jit entry point '{wj.name}' compiled {wj.traces} "
                    f"traces (budget {wj.max_traces}) — one-trace-per-"
                    "plan contract broken (see lint R001 / PR 7)")

    def report(self) -> dict:
        return {name: {"traces": wj.traces,
                       "max_traces": wj.max_traces,
                       "calls": wj.calls,
                       "compile_s": wj.compile_s}
                for name, wj in sorted(self.entries.items())}

    def export(self, registry) -> None:
        """Publish the ledger into a MetricsRegistry (report boundary)."""
        g_tr = registry.gauge("jit_traces",
                              "compiled trace count per jit entry point")
        g_bud = registry.gauge("jit_trace_budget",
                               "allowed traces (-1 = unbounded)")
        g_cs = registry.gauge("jit_compile_s",
                              "wall seconds of trace-growing calls")
        c_calls = registry.counter("jit_calls", "calls per entry point")
        for name, wj in sorted(self.entries.items()):
            lab = {"entry": name}
            g_tr.set(wj.traces, **lab)
            g_bud.set(-1 if wj.max_traces is None else wj.max_traces,
                      **lab)
            g_cs.set(wj.compile_s, **lab)
            c_calls.inc(wj.calls - c_calls.value(**lab), **lab)

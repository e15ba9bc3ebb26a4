"""Engine-loop phase spans and per-request timelines.

`Tracer.span(name, **args)` is the one span API of the serving path. Each
span goes to three sinks at once:

  * the JAX profiler: a `jax.profiler.TraceAnnotation`, so the span lands
    in the profiler's host plane, on the clock the device events are
    converted to, whenever a trace is being captured (and costs a
    disabled TraceMe otherwise);
  * the metrics registry: its host-clock duration (`obs/clock.now`) is
    observed into `serve_phase_s{phase=<name>}`, always, by the same code
    path whether or not anyone reads it;
  * a `TraceBuffer`, when one is attached: a Chrome "X" slice on the
    engine track, its `ts` measured from `Tracer.origin` (the engine sets
    it to the start of `run`'s loop, the timebase of every event the
    engine writes there).

Spans nest by time on the engine's one thread: a span's parent is the
span open around it, in the profiler's trace and in the Chrome file alike.
Spans of one request carry its `rid`; a decode step carries its `live`
slot count. While `Tracer.gc_spans()` is open, each garbage collection is
a `serve.gc` span (through `gc.callbacks`).

The TraceBuffer holds Chrome trace-event JSON, so the file loads directly
in Perfetto / chrome://tracing. Layout convention used by
`launch/scheduler`:

  * pid ENGINE_PID ("engine"), tid 0: the `serve.*` phase slices, nested
    in one `serve.iter` slice per loop iteration, plus "occupancy"
    counter tracks (occupied slots, prefill queue, pending arrivals).
  * pid REQUEST_PID ("requests"), one tid PER REQUEST (tid = rid): a
    "request" slice spanning arrival -> finish, with that request's
    "prefill_chunk" / "decode" child slices nested inside it — Chrome
    nests same-thread slices by interval containment, which the engine
    guarantees by emitting children only between admit and finish.

Every slice also carries the raw seconds (`dur_s`) in `args`, so tests
and tools can reconcile span sums against the engine's reported latency
stats without round-tripping through the microsecond floats.
"""
from __future__ import annotations

import contextlib
import gc
import json
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from . import clock

ENGINE_PID = 1
REQUEST_PID = 2


class TraceBuffer:
    """Append-only list of Chrome trace events (host-side, no clock of
    its own — callers pass seconds from their origin on `obs/clock`)."""

    def __init__(self):
        self.events: List[dict] = []
        self._named: set = set()

    # ------------------------------------------------------------ naming

    def name_process(self, pid: int, name: str) -> None:
        if ("process", pid) in self._named:
            return
        self._named.add(("process", pid))
        self.events.append({"ph": "M", "name": "process_name", "pid": pid,
                            "tid": 0, "args": {"name": name}})

    def name_thread(self, pid: int, tid: int, name: str) -> None:
        if ("thread", pid, tid) in self._named:
            return
        self._named.add(("thread", pid, tid))
        self.events.append({"ph": "M", "name": "thread_name", "pid": pid,
                            "tid": tid, "args": {"name": name}})

    # ------------------------------------------------------------ events

    def complete(self, name: str, ts_s: float, dur_s: float, *,
                 pid: int = ENGINE_PID, tid: int = 0, cat: str = "serve",
                 args: Optional[Dict] = None) -> None:
        """One complete ("X") slice; ts/dur in SECONDS from the origin."""
        a = dict(args or {})
        a["dur_s"] = dur_s
        self.events.append({"ph": "X", "name": name, "cat": cat,
                            "pid": pid, "tid": tid,
                            "ts": ts_s * 1e6, "dur": dur_s * 1e6,
                            "args": a})

    def instant(self, name: str, ts_s: float, *, pid: int = ENGINE_PID,
                tid: int = 0, cat: str = "serve",
                args: Optional[Dict] = None) -> None:
        self.events.append({"ph": "i", "name": name, "cat": cat,
                            "pid": pid, "tid": tid, "ts": ts_s * 1e6,
                            "s": "t", "args": dict(args or {})})

    def counter(self, name: str, ts_s: float, values: Dict[str, float], *,
                pid: int = ENGINE_PID) -> None:
        self.events.append({"ph": "C", "name": name, "pid": pid, "tid": 0,
                            "ts": ts_s * 1e6, "args": dict(values)})

    # ------------------------------------------------------------ export

    def to_dict(self) -> dict:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def to_json(self, **json_kw) -> str:
        json_kw.setdefault("indent", None)
        return json.dumps(self.to_dict(), **json_kw)

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())
            f.write("\n")


class _Span:
    """One span (`Tracer.span`); after the body, `t0` and `t1` hold its
    start and end on `obs/clock`."""
    __slots__ = ("_tracer", "_name", "_args", "_note", "t0", "t1")

    def __init__(self, tracer: "Tracer", name: str, args: Dict):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self) -> "_Span":
        self._note = TraceAnnotation(self._name, **self._args)
        self._note.__enter__()
        self.t0 = clock.now()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = clock.now()
        self._note.__exit__(*exc)
        self._tracer._record(self._name, self.t0, self.t1, self._args)


class Tracer:
    """Named host spans of the serving loop (see the module docstring):
    profiler annotation, `serve_phase_s{phase=...}` histogram and, with a
    TraceBuffer attached, a Chrome slice on the engine track."""

    def __init__(self, registry, buffer: Optional[TraceBuffer] = None):
        self._hist = registry.histogram(
            "serve_phase_s", "host seconds per engine-loop phase span")
        self.buffer = buffer
        # obs/clock time of the Chrome file's ts 0; slices are written
        # only while it is set
        self.origin: Optional[float] = None
        self._series: Dict[str, object] = {}    # span name -> bound series
        self._gc: Optional[_Span] = None

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def _record(self, name: str, t0: float, t1: float, args: Dict) -> None:
        series = self._series.get(name)
        if series is None:
            series = self._series[name] = self._hist.bind(phase=name)
        series.observe(t1 - t0)
        if self.buffer is not None and self.origin is not None:
            self.buffer.complete(name, t0 - self.origin, t1 - t0,
                                 args=args)

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._gc = self.span("serve.gc", generation=info["generation"])
            self._gc.__enter__()
        elif self._gc is not None:
            span, self._gc = self._gc, None
            span.__exit__(None, None, None)

    @contextlib.contextmanager
    def gc_spans(self):
        """Record each garbage collection inside the body as a `serve.gc`
        span; the callback is removed on exit."""
        gc.callbacks.append(self._on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(self._on_gc)

"""Profiler trace of the measured window, and its reduction to numbers.

`capture()` records the JAX profiler's trace (python tracer off) into a
temporary directory and `load()` keeps only what the readers need:

  device:  {plane: [(op name, start_ns, duration_ns), ...]} from each TPU
           plane's "XLA Ops" line (one event per executed HLO op or kernel);
  modules: {plane: [(module name, start_ns, duration_ns), ...]} from each
           TPU plane's "XLA Modules" line (one event per program run);
  program: [(span name, start_ns, duration_ns), ...] of the program's own
           `serve.*` spans (the engine loop's phases, `repro.obs.trace`);
  host:    the same of the benchmark's own spans (the measured window).

The reduction works on those plain lists, so it can be checked on a small
recorded trace (tests/bench/data).
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
PROGRAM_PREFIX = "serve."
NO_SPAN = "host: no program span"


@contextlib.contextmanager
def capture():
    """Trace the body; yields a dict filled with load()'s result on exit."""
    import jax
    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        try:
            out.update(load(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def load(log_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    device: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    program: List[Event] = []
    lines_seen: Dict[str, List[str]] = {}
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                lines_seen[plane.name] = [ln.name for ln in plane.lines]
                for ln in plane.lines:
                    if ln.name == OPS_LINE:
                        device.setdefault(plane.name, []).extend(
                            (op_name(e.name), e.start_ns, e.duration_ns)
                            for e in ln.events)
                    elif ln.name == MODULES_LINE:
                        modules.setdefault(plane.name, []).extend(
                            (e.name, e.start_ns, e.duration_ns)
                            for e in ln.events)
            elif plane.name == HOST_PLANE:
                for ln in plane.lines:
                    for e in ln.events:
                        if e.name.startswith(SPAN_PREFIX):
                            host.append((e.name, e.start_ns, e.duration_ns))
                        elif e.name.startswith(PROGRAM_PREFIX):
                            program.append((e.name, e.start_ns,
                                            e.duration_ns))
    for evs in list(device.values()) + list(modules.values()) \
            + [host, program]:
        evs.sort(key=lambda e: e[1])
    return {"device": device, "modules": modules, "host": host,
            "program": program, "lines": lines_seen}


# ------------------------------------------------------------ reduction

# control-flow ops whose events span the ops they run, which have events
# of their own
WRAPPERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(text: str) -> str:
    """The HLO instruction name of an "XLA Ops" event, whose name on a TPU
    is the instruction's whole text ("%cim_mvm_packed_pallas.48 = f32[..."):
    "cim_mvm_packed_pallas.48"."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event]) -> float:
    """Nanoseconds in which some operation ran (union of op intervals)."""
    return sum(e - s for s, e in union((s, s + d) for _, s, d in events))


def kernel_ns(events: Sequence[Event], pattern: str) -> Tuple[float, int]:
    """(summed device time, event count) of ops whose name matches."""
    rx = re.compile(pattern)
    hits = [d for n, _, d in events if rx.search(n)]
    return float(sum(hits)), len(hits)


def top_ops(events: Sequence[Event], k: int = 10) -> List[List]:
    """The k op names that took most device time, in seconds; control-flow
    ops, whose time is that of the ops inside them, are left out."""
    tot: Dict[str, float] = {}
    for n, _, d in events:
        if not WRAPPERS.match(n):
            tot[n] = tot.get(n, 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d * 1e-9] for n, d in best]


def idle_intervals(events: Sequence[Event], window: Tuple[float, float]
                   ) -> List[Tuple[float, float]]:
    """[start, end) of each stretch of the window in which no op ran."""
    w0, w1 = window
    out, prev = [], w0
    for s, e in union((max(s, w0), min(s + d, w1)) for _, s, d in events
                      if s + d > w0 and s < w1) + [(w1, w1)]:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    return out


def innermost(spans: Sequence[Event]) -> List[Tuple[float, float, str]]:
    """[(start, end, name)] cutting the spans' time into pieces, each
    named by the innermost span open over it (spans of one thread nest)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []       # (end, name) of open spans
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for n, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        close_until(s)
        if stack and s > cursor:
            out.append((cursor, s, stack[-1][1]))
        # a child never outlives its parent
        e = min(s + d, stack[-1][0]) if stack else s + d
        stack.append((e, n))
        cursor = s
    close_until(float("inf"))
    return out


def idle_gaps(events: Sequence[Event], program: Sequence[Event],
              window: Tuple[float, float], offset: float = 0.0,
              k: int = 10) -> List[List]:
    """The k longest idle gaps of the device inside `window`, in seconds,
    each named by the innermost program span that covered most of it, or
    NO_SPAN where none did. `offset` moves device times onto the program
    spans' clock (bench/phases.align)."""
    gaps = sorted(idle_intervals(events, window),
                  key=lambda g: g[0] - g[1])[:k]
    pieces = innermost(program)
    starts = [p[0] for p in pieces]
    out = []
    for g0, g1 in gaps:
        h0, h1 = g0 + offset, g1 + offset
        cover: Dict[str, float] = {}
        # pieces are disjoint and sorted: start one before the first that
        # begins inside the gap
        for ps, pe, name in pieces[max(bisect.bisect_left(starts, h0) - 1,
                                       0):]:
            if ps >= h1:
                break
            c = min(h1, pe) - max(h0, ps)
            if c > 0:
                cover[name] = cover.get(name, 0.0) + c
        best = max(cover, key=cover.get) if cover else NO_SPAN
        out.append([best, (g1 - g0) * 1e-9])
    return out


def window_of(host: Sequence[Event]) -> Optional[Tuple[float, float]]:
    """[start, end) of the traced window span, in the trace's ns."""
    for n, s, d in host:
        if n == WINDOW_SPAN:
            return (s, s + d)
    return None


def device_events(trace: dict) -> List[Event]:
    """Ops of the one device the cells use (the first TPU plane)."""
    planes = sorted(trace["device"])
    return trace["device"][planes[0]] if planes else []


def in_window(events: Sequence[Event], window: Tuple[float, float]
              ) -> List[Event]:
    """Events clipped to the window."""
    w0, w1 = window
    out = []
    for n, s, d in events:
        s2, e2 = max(s, w0), min(s + d, w1)
        if e2 > s2:
            out.append((n, s2, e2 - s2))
    return out

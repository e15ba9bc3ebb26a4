"""Profiler trace of the measured window, and its reduction to numbers.

`capture()` records the JAX profiler's trace (python tracer off) into a
temporary directory and `load()` keeps only what the readers need:

  device: {plane: [(op name, start_ns, duration_ns), ...]} from each TPU
          plane's "XLA Ops" line (one event per executed HLO op or kernel);
  host:   [(span name, start_ns, duration_ns), ...] of the benchmark's own
          TraceAnnotation spans around the engine's calls.

The reduction works on those plain lists, so it can be checked on a small
recorded trace (tests/bench/data).
"""
from __future__ import annotations

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
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


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
    host: List[Event] = []
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
            elif plane.name == HOST_PLANE:
                for ln in plane.lines:
                    host.extend((e.name, e.start_ns, e.duration_ns)
                                for e in ln.events
                                if e.name.startswith(SPAN_PREFIX))
    for evs in device.values():
        evs.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return {"device": device, "host": host, "lines": lines_seen}


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


def idle_gaps(events: Sequence[Event], host: Sequence[Event],
              window: Tuple[float, float], k: int = 10) -> List[List]:
    """The k longest idle gaps of the device inside `window`, each named by
    the engine call (host span) that covered most of it, or "host: between
    engine calls" where none did, in seconds."""
    busy = union((s, s + d) for _, s, d in events)
    w0, w1 = window
    gaps = []
    prev = w0
    for s, e in busy:
        if s > prev:
            gaps.append((max(prev, w0), min(s, w1)))
        prev = max(prev, e)
    if prev < w1:
        gaps.append((prev, w1))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:k]
    out = []
    for g0, g1 in gaps:
        best, cover = "host: between engine calls", 0.0
        for n, s, d in host:
            if n == WINDOW_SPAN:
                continue
            c = min(g1, s + d) - max(g0, s)
            if c > cover:
                best, cover = n, c
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

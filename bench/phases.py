"""The program's own engine-loop phase spans in a profiler trace: aligning
their clock with the device's, and naming the device's idle time by what
the host was doing.

The engine records every loop phase as a `serve.*` span
(`repro.obs.trace.Tracer`), which the profiler keeps on its host plane;
`devtrace.load()` returns them as `program`, and each TPU plane's program
runs as `modules`.

The profiler converts device events to the host's clock only roughly: on
a TPU v5e they read one to two milliseconds early. `align()` measures the
constant offset of one window from the engine's own steps: the i-th
dispatch of a packed step (`serve.dispatch.pool_decode` or
`serve.dispatch.slot_prefill`, in start order) owns the i-th run of a
program that holds packed-kernel events, and each such run has to lie
between its dispatch's start and the end of the wait that follows it.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench import devtrace
from bench.devtrace import Event

# a packed step's dispatch span -> the span that waits for its result
STEP_WAIT = {"serve.dispatch.pool_decode": "serve.decode.wait",
             "serve.dispatch.slot_prefill": "serve.prefill.wait"}
SLEEP = "serve.sleep"
# the packed CIM kernel's events (instruction names on a TPU v5e)
KERNEL = r"^cim_mvm_packed_pallas"
UNATTRIBUTED = ""            # idle time under no program span


def device_modules(trace: dict) -> List[Event]:
    """Program runs of the device devtrace.device_events reads."""
    planes = sorted(trace["device"])
    return trace["modules"].get(planes[0], []) if planes else []


def is_host_phase(name: str) -> bool:
    """A span in which the host, not the device, holds the loop: every
    program span but the waits for a step and the sleep for an arrival."""
    return bool(name) and not name.endswith(".wait") and name != SLEEP


def step_intervals(program: Sequence[Event]) -> List[Tuple[float, float]]:
    """(dispatch start, end of its wait) of every packed step, in order."""
    out = []
    want = None
    for n, s, d in program:
        if n in STEP_WAIT:
            want, start = STEP_WAIT[n], s
        elif n == want:
            out.append((start, s + d))
            want = None
    return out


def step_runs(modules: Sequence[Event], device: Sequence[Event]
              ) -> List[Tuple[float, float]]:
    """[start, end) of every program run that holds a packed-kernel event:
    the packed steps, in order."""
    kern = sorted(s for n, s, _ in device if re.search(KERNEL, n))
    out = []
    for _, s, d in sorted(modules, key=lambda e: e[1]):
        i = bisect.bisect_left(kern, s)
        if i < len(kern) and kern[i] < s + d:
            out.append((s, s + d))
    return out


def align(program: Sequence[Event], runs: Sequence[Tuple[float, float]]
          ) -> Optional[Tuple[float, float]]:
    """(offset_ns, width_ns): the offset to add to device times to put them
    on the program spans' clock (the middle of the offsets that place every
    step's run inside its dispatch-to-wait interval) and the width of that
    interval. None where the runs do not pair one to one with the steps,
    or where no single offset fits every step."""
    steps = step_intervals(program)
    if not steps or len(runs) != len(steps):
        return None
    lo = max(s - r0 for (s, _), (r0, _) in zip(steps, runs))
    hi = min(e - r1 for (_, e), (_, r1) in zip(steps, runs))
    if lo > hi:
        return None
    return (lo + hi) / 2, hi - lo


def idle_by_phase(device: Sequence[Event], program: Sequence[Event],
                  window: Tuple[float, float], offset: float
                  ) -> Dict[str, float]:
    """Nanoseconds of the window in which no op ran on the device, by the
    innermost program span open then (UNATTRIBUTED where none was), with
    device times moved onto the program's clock by `offset`."""
    gaps = [(g0 + offset, g1 + offset)
            for g0, g1 in devtrace.idle_intervals(device, window)]
    pieces = devtrace.innermost(program)
    out: Dict[str, float] = {}
    j = 0
    for g0, g1 in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            s, e, name = pieces[k]
            c = min(e, g1) - max(s, g0)
            if c > 0:
                out[name] = out.get(name, 0.0) + c
                covered += c
            k += 1
        out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) \
            + (g1 - g0) - covered
    return out


def idle_host_pct(device: Sequence[Event], modules: Sequence[Event],
                  program: Sequence[Event], window: Tuple[float, float]
                  ) -> Tuple[Optional[float], Optional[Tuple[float, float]]]:
    """(share of the window in % in which the device was idle while a host
    phase was the innermost program span, the alignment it used). The
    share is None where `align` finds no offset."""
    found = align(program, step_runs(modules, device))
    if found is None:
        return None, None
    by = idle_by_phase(device, program, window, found[0])
    host = sum(v for name, v in by.items() if is_host_phase(name))
    return 100.0 * host / (window[1] - window[0]), found

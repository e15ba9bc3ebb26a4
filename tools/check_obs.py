#!/usr/bin/env python
"""Schema + invariant validation for serving telemetry exports.

CI's metrics-smoke runs `serve --traffic --metrics-out --trace-out` and
hands the files here. Three layers of checks, all on the EXPORTED files
(not in-process state), so the validation covers the full write/read
round trip an external dashboard would do:

  1. Metrics JSON schema (obs/metrics.MetricsRegistry.to_dict): the
     counters/gauges/histograms shape, non-negative counters, histogram
     buckets cumulative-monotone with a trailing +Inf (le=None) bucket
     whose count equals the exact count.
  2. Chrome-trace JSON (obs/trace.TraceBuffer.to_dict): a traceEvents
     list of X/i/C/M phase events with the fields Perfetto needs; every
     "X" span carries its exact seconds in args.dur_s; the engine's
     `serve.*` phase slices nest inside a `serve.iter` slice of their
     track (`serve.gc` excepted: a collection starts at whichever
     allocation triggers it, between iterations too).
  3. Serving invariants: the one-decode-trace contract
     (jit_traces{entry="pool_decode"} == 1 — the PR 7 retrace bug class,
     lint R001's runtime twin; on a merged multi-rank export the check
     holds PER rank-labeled series, and --expect-ranks N requires ranks
     0..N-1 all present) and exact chip-energy reconciliation — for
     every labeled series ({chip, direction}, plus {rank} on merged
     multi-process exports),
     chip_energy_pj == chip_pj_per_mvm * chip_mvm_dispatches with no
     float drift (the meter stores integer dispatch counts and takes one
     product at export; see obs/chipmeter).

Usage (exits non-zero on the first violated check):

    python tools/check_obs.py --metrics M.json [--trace T.json]
        [--no-decode-contract] [--expect-ranks N]
"""
from __future__ import annotations

import argparse
import bisect
import json
import sys

TRACE_PHASES = {"X", "i", "C", "M"}
PHASE_PREFIX = "serve."
ITER_PHASE = "serve.iter"
FREE_PHASES = {ITER_PHASE, "serve.gc"}   # need no enclosing serve.iter
NEST_SLACK_US = 1e-3                     # float rounding of ts + dur


class CheckError(Exception):
    pass


def _fail(msg: str) -> None:
    raise CheckError(msg)


def _require(cond: bool, msg: str) -> None:
    if not cond:
        _fail(msg)


# ------------------------------------------------------------- metrics

def check_metrics_schema(doc: dict) -> None:
    _require(isinstance(doc, dict) and
             set(doc) == {"counters", "gauges", "histograms"},
             "metrics: top level must be {counters, gauges, histograms}, "
             f"got {sorted(doc) if isinstance(doc, dict) else type(doc)}")
    for kind in ("counters", "gauges"):
        for e in doc[kind]:
            _require(set(e) == {"name", "labels", "value"},
                     f"metrics: {kind} entry keys {sorted(e)}")
            _require(isinstance(e["name"], str) and e["name"],
                     f"metrics: unnamed {kind} entry")
            _require(isinstance(e["labels"], dict),
                     f"metrics: {e['name']}: labels must be a dict")
            _require(isinstance(e["value"], (int, float)),
                     f"metrics: {e['name']}: non-numeric value")
            if kind == "counters":
                _require(e["value"] >= 0,
                         f"metrics: counter {e['name']} is negative")
    for h in doc["histograms"]:
        _require(set(h) == {"name", "labels", "count", "sum", "min",
                            "max", "buckets"},
                 f"metrics: histogram entry keys {sorted(h)}")
        name = h["name"]
        _require(h["count"] >= 0, f"metrics: {name}: negative count")
        if h["count"] == 0:
            _require(h["min"] is None and h["max"] is None,
                     f"metrics: {name}: empty series with extremes")
        else:
            _require(h["min"] <= h["max"],
                     f"metrics: {name}: min > max")
        buckets = h["buckets"]
        _require(buckets and buckets[-1][0] is None,
                 f"metrics: {name}: missing trailing +Inf bucket")
        prev_le, prev_cum = -float("inf"), 0
        for le, cum in buckets:
            _require(le is None or le > prev_le,
                     f"metrics: {name}: bucket bounds not increasing")
            _require(cum >= prev_cum,
                     f"metrics: {name}: cumulative counts decrease")
            prev_le = le if le is not None else prev_le
            prev_cum = cum
        _require(buckets[-1][1] == h["count"],
                 f"metrics: {name}: +Inf cumulative {buckets[-1][1]} != "
                 f"count {h['count']}")


def _series(doc: dict, kind: str, name: str) -> dict:
    """{frozen labels -> value} for one metric family."""
    return {tuple(sorted(e["labels"].items())): e["value"]
            for e in doc[kind] if e["name"] == name}


def check_decode_contract(doc: dict, expect_ranks: int = 0) -> None:
    """Every jit_traces series tagged entry=pool_decode must equal 1 —
    PER RANK: a merged multi-rank export (obs.metrics.merge_registries)
    carries one such series per rank label, and each one is the
    one-decode-trace contract for that replica. expect_ranks > 0
    additionally requires the rank labels 0..N-1 to all be present (a
    dropped rank's metrics would otherwise vanish silently from the
    merge)."""
    traces = _series(doc, "gauges", "jit_traces")
    decode = {lab: v for lab, v in traces.items()
              if ("entry", "pool_decode") in lab}
    _require(bool(decode),
             "metrics: no jit_traces{entry=\"pool_decode\"} series — was "
             "the engine's jitwatch exported?")
    for lab, v in sorted(decode.items()):
        _require(v == 1,
                 f"one-decode-trace contract broken on {dict(lab)}: "
                 f"jit_traces == {v} (expected 1)")
    if expect_ranks > 0:
        ranks = {dict(lab).get("rank") for lab in decode}
        want = {str(r) for r in range(expect_ranks)}
        _require(ranks == want,
                 f"metrics: decode-contract rank labels {sorted(ranks, key=str)} "
                 f"!= expected ranks {sorted(want)}")
    budgets = _series(doc, "gauges", "jit_trace_budget")
    for lab, n in traces.items():
        budget = budgets.get(lab, -1)
        _require(budget < 0 or n <= budget,
                 f"jit trace budget exceeded on {dict(lab)}: "
                 f"{n} traces > budget {budget}")


def check_energy_reconciliation(doc: dict) -> int:
    """chip_energy_pj == chip_pj_per_mvm * chip_mvm_dispatches, exactly,
    per labeled series. Returns the number of series reconciled."""
    pj = _series(doc, "gauges", "chip_pj_per_mvm")
    energy = _series(doc, "gauges", "chip_energy_pj")
    mvms = _series(doc, "counters", "chip_mvm_dispatches")
    _require(set(pj) == set(energy) == set(mvms),
             "metrics: chip_* families disagree on labeled series: "
             f"pj_per_mvm {len(pj)}, energy {len(energy)}, "
             f"dispatches {len(mvms)}")
    for lab in sorted(pj):
        n = mvms[lab]
        _require(n == int(n) and n >= 0,
                 f"metrics: non-integer dispatch count on {dict(lab)}")
        want = pj[lab] * n
        _require(energy[lab] == want,
                 f"chip energy does not reconcile on {dict(lab)}: "
                 f"chip_energy_pj {energy[lab]!r} != pj_per_mvm "
                 f"{pj[lab]!r} * {int(n)} dispatches == {want!r}")
    return len(pj)


# --------------------------------------------------------------- trace

def check_trace_schema(doc: dict) -> int:
    """Chrome trace-event JSON shape. Returns the event count."""
    _require(isinstance(doc, dict) and "traceEvents" in doc,
             "trace: missing traceEvents")
    _require(doc.get("displayTimeUnit") in ("ms", "ns"),
             f"trace: bad displayTimeUnit {doc.get('displayTimeUnit')!r}")
    events = doc["traceEvents"]
    _require(isinstance(events, list) and events, "trace: no events")
    for ev in events:
        ph = ev.get("ph")
        _require(ph in TRACE_PHASES,
                 f"trace: unknown phase {ph!r} on {ev.get('name')!r}")
        _require(isinstance(ev.get("name"), str) and ev["name"],
                 "trace: unnamed event")
        _require(isinstance(ev.get("pid"), int),
                 f"trace: {ev['name']}: missing pid")
        if ph == "M":
            continue
        _require(isinstance(ev.get("ts"), (int, float)) and ev["ts"] >= 0,
                 f"trace: {ev['name']}: bad ts")
        if ph == "X":
            _require(ev.get("dur", -1) >= 0,
                     f"trace: span {ev['name']}: bad dur")
            dur_s = ev.get("args", {}).get("dur_s")
            _require(isinstance(dur_s, (int, float)),
                     f"trace: span {ev['name']}: args.dur_s missing — "
                     "exact seconds must ride along the rounded us")
        if ph == "C":
            args = ev.get("args", {})
            _require(args and all(isinstance(v, (int, float))
                                  for v in args.values()),
                     f"trace: counter {ev['name']}: non-numeric series")
    return len(events)


def check_phase_nesting(doc: dict) -> int:
    """Every `serve.*` phase slice lies inside a `serve.iter` slice of the
    same track. Returns the number of phase slices checked."""
    spans = [ev for ev in doc["traceEvents"] if ev.get("ph") == "X"
             and ev["name"].startswith(PHASE_PREFIX)]
    iters: dict = {}
    for ev in spans:
        if ev["name"] == ITER_PHASE:
            iters.setdefault((ev["pid"], ev.get("tid")), []).append(
                (ev["ts"], ev["ts"] + ev["dur"]))
    for track in iters.values():
        track.sort()
    n = 0
    for ev in spans:
        if ev["name"] in FREE_PHASES:
            continue
        track = iters.get((ev["pid"], ev.get("tid")), [])
        i = bisect.bisect_right(track, (ev["ts"] + NEST_SLACK_US,
                                        float("inf"))) - 1
        _require(i >= 0 and ev["ts"] + ev["dur"]
                 <= track[i][1] + NEST_SLACK_US,
                 f"trace: phase {ev['name']} at ts {ev['ts']} lies "
                 f"outside every {ITER_PHASE} slice of its track")
        n += 1
    return n


# ----------------------------------------------------------------- cli

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate serve --metrics-out/--trace-out exports")
    ap.add_argument("--metrics", required=True,
                    help="metrics JSON (MetricsRegistry.to_dict)")
    ap.add_argument("--trace", default="",
                    help="Chrome trace-event JSON (TraceBuffer.to_dict)")
    ap.add_argument("--no-decode-contract", action="store_true",
                    help="skip the jit_traces{entry=pool_decode}==1 check "
                         "(for exports from non-engine paths)")
    ap.add_argument("--expect-ranks", type=int, default=0,
                    help="require a merged multi-rank export with exactly "
                         "this many rank labels on the decode-contract "
                         "series (0 = don't check rank structure)")
    args = ap.parse_args(argv)
    try:
        with open(args.metrics) as f:
            metrics = json.load(f)
        check_metrics_schema(metrics)
        if not args.no_decode_contract:
            check_decode_contract(metrics, expect_ranks=args.expect_ranks)
        n_chips = check_energy_reconciliation(metrics)
        n_events = n_phases = 0
        if args.trace:
            with open(args.trace) as f:
                trace = json.load(f)
            n_events = check_trace_schema(trace)
            n_phases = check_phase_nesting(trace)
    except CheckError as e:
        print(f"check_obs: FAIL: {e}", file=sys.stderr)
        return 1
    msg = (f"check_obs: OK — {n_chips} chip series reconcile exactly"
           + ("" if args.no_decode_contract
              else ", decode trace contract holds"))
    if args.trace:
        msg += (f", {n_events} trace events well-formed, {n_phases} "
                "phase slices nested in serve.iter")
    print(msg)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

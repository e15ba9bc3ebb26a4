"""Serving telemetry (src/repro/obs): registry semantics, chip-meter
energy reconciliation against core/energy.mvm_cost, Chrome-trace span
timelines, the jit-cache watchdog, and the zero-perturbation contract —
serving with metrics + tracing on emits BITWISE the same tokens as
serving with them off."""
import gc
import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.core.energy import mvm_cost
from repro.launch.scheduler import ContinuousBatchingEngine, Request
from repro.launch.steps import arch_serving
from repro.obs import MetricsRegistry, TraceBuffer
from repro.obs.chipmeter import ChipMeter
from repro.obs.jitwatch import JitRetraceError, JitWatcher
from repro.obs.trace import ENGINE_PID, REQUEST_PID

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_obs  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402


def _cfg(arch="gemma2-9b", cim=False):
    cfg = configs.get(arch, smoke=True).replace(dtype=jnp.float32)
    if cim:
        cfg = cfg.replace(cim_mode="packed", moe_dropless=True)
    return cfg


def _params(cfg, cim=False):
    sv = arch_serving(cfg)
    params = sv.init_params(jax.random.PRNGKey(0))
    if cim:
        params = sv.deploy_cim(jax.random.PRNGKey(7), params, mode="ideal",
                               mesh_shape={"model": 1})
    return params


def _requests(cfg, lens, gens, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        (lens[i],)).astype(np.int32),
                    max_new=gens[i]) for i in range(len(lens))]


# ------------------------------------------------------------- registry

def test_registry_counter_gauge_semantics():
    r = MetricsRegistry()
    c = r.counter("reqs", "requests")
    c.inc()
    c.inc(2, arch="a")
    assert c.value() == 1 and c.value(arch="a") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("occ", "occupancy")
    g.set(3, slot="0")
    g.set(1, slot="0")
    assert g.value(slot="0") == 1
    # idempotent re-registration returns the SAME family; kind clash raises
    assert r.counter("reqs") is c
    with pytest.raises(ValueError):
        r.gauge("reqs")
    assert r.value("reqs", arch="a") == 2
    assert r.value("absent") == 0.0


def test_registry_histogram_quantiles_and_export():
    r = MetricsRegistry()
    h = r.histogram("lat_s", "latency")
    vals = [0.001, 0.002, 0.004, 0.008, 0.1]
    for v in vals:
        h.observe(v)
    assert h.count() == 5
    assert h.sum() == pytest.approx(sum(vals))
    # exact extremes; interior quantiles bucket-interpolated but monotone
    assert h.quantile(0.0) == pytest.approx(min(vals))
    assert h.quantile(1.0) == pytest.approx(max(vals))
    qs = [h.quantile(q) for q in (0.1, 0.5, 0.9)]
    assert qs == sorted(qs)
    assert min(vals) <= qs[0] and qs[-1] <= max(vals)
    d = r.to_dict()
    (hist,) = d["histograms"]
    assert hist["count"] == 5 and hist["min"] == min(vals)
    # cumulative bucket counts end at the total, final bound is +Inf (None)
    assert hist["buckets"][-1] == [None, 5]
    assert all(b0[1] <= b1[1] for b0, b1 in zip(hist["buckets"],
                                                hist["buckets"][1:]))
    prom = r.to_prometheus()
    assert '# TYPE lat_s histogram' in prom
    assert 'lat_s_bucket{le="+Inf"} 5' in prom
    assert "lat_s_count 5" in prom
    # the JSON export round-trips
    assert json.loads(r.to_json())["histograms"][0]["count"] == 5


# ------------------------------------------------------------ chipmeter

def test_chipmeter_reconciles_with_mvm_cost_exactly():
    """For a deployed packed stack, per-chip cumulative energy equals
    mvm_cost(rows, cols, bits).energy_pj * dispatches EXACTLY — the meter
    stores integer dispatch counts and prices them through the same model
    bench_mapping's precision rows use."""
    cfg = _cfg(cim=True)
    params = _params(cfg, cim=True)
    meter = ChipMeter.from_params(params, cfg.cim_in_bits, cfg.cim_out_bits)
    assert meter.entries, "deployed gemma2 stack must expose packed chips"
    meter.count_rows(7)
    meter.count_rows(3)
    for (name, direction), e in meter.entries.items():
        n = meter.mvm_dispatches(name, direction)
        assert n == 10 * e.n_stack
        cost = mvm_cost(e.rows, e.cols, e.in_bits, e.out_bits)
        assert meter.energy_pj(name, direction) == cost.energy_pj * n
    # totals are the sum of the per-entry exact products
    assert meter.energy_pj() == sum(
        meter.entries[k].cost.energy_pj * meter.mvm_dispatches(*k)
        for k in meter.entries)
    # ... and one row through the whole stack is the per-token cost
    assert meter.per_token_pj() * 10 == pytest.approx(meter.energy_pj())


def test_chipmeter_export_keeps_the_invariant():
    cfg = _cfg(cim=True)
    params = _params(cfg, cim=True)
    meter = ChipMeter.from_params(params, cfg.cim_in_bits, cfg.cim_out_bits)
    meter.count_rows(5)
    r = MetricsRegistry()
    meter.export(r)
    meter.count_rows(6)
    meter.export(r)                      # re-export must not drift
    for (name, direction), e in meter.entries.items():
        lab = {"chip": name, "direction": direction}
        n = r.value("chip_mvm_dispatches", **lab)
        assert n == meter.mvm_dispatches(name, direction)
        assert r.value("chip_energy_pj", **lab) == \
            r.value("chip_pj_per_mvm", **lab) * n


def test_chipmeter_report_schema():
    cfg = _cfg(cim=True)
    params = _params(cfg, cim=True)
    meter = ChipMeter.from_params(params, cfg.cim_in_bits, cfg.cim_out_bits)
    meter.count_rows(2)
    rep = meter.report()
    assert rep["total_mvm_dispatches"] == meter.mvm_dispatches()
    for row in rep["chips"]:
        assert row["energy_pj"] == row["pj_per_mvm"] * row["mvm_dispatches"]


# ------------------------------------------------------------- jitwatch

def test_jitwatch_counts_traces_and_budget():
    w = JitWatcher()
    f = w.wrap("f", lambda x: x * 2, max_traces=1)
    f(jnp.zeros((2,)))
    f(jnp.ones((2,)))                    # same shape: no new trace
    assert f.traces == 1 and f.calls == 2
    f(jnp.zeros((3,)))                   # new shape: retrace (non-strict)
    assert f.traces == 2 and f.over_budget
    assert f._cache_size() == 2          # the raw counter is preserved
    with pytest.raises(JitRetraceError):
        w.check()
    rep = w.report()["f"]
    assert rep["traces"] == 2 and rep["compile_s"] > 0


def test_jitwatch_strict_and_sealed_raise_at_the_call():
    w = JitWatcher(strict=True)
    f = w.wrap("f", lambda x: x + 1, max_traces=1)
    f(jnp.zeros((2,)))
    with pytest.raises(JitRetraceError, match="'f'"):
        f(jnp.zeros((3,)))               # over budget under strict
    w2 = JitWatcher()
    g = w2.wrap("g", lambda x: x + 1)    # unbounded budget...
    g(jnp.zeros((2,)))
    w2.seal()                            # ...but sealed after warmup
    g(jnp.zeros((2,)))                   # warmed shape: fine
    with pytest.raises(JitRetraceError, match="sealed"):
        g(jnp.zeros((4,)))


def test_jitwatch_export():
    w = JitWatcher()
    f = w.wrap("decode", lambda x: x, max_traces=1)
    f(jnp.zeros((2,)))
    r = MetricsRegistry()
    w.export(r)
    assert r.value("jit_traces", entry="decode") == 1
    assert r.value("jit_trace_budget", entry="decode") == 1
    assert r.value("jit_calls", entry="decode") == 1


# ------------------------------------------------- engine + trace spans

def test_engine_trace_is_valid_chrome_json_with_nested_spans(tmp_path):
    cfg = _cfg()
    params = _params(cfg)
    reqs = _requests(cfg, [32, 64, 32], [4, 3, 2])
    trace = TraceBuffer()
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=96,
                                   trace=trace)
    eng.run(reqs, realtime=False)
    path = tmp_path / "trace.json"
    trace.write(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert all(ev["ph"] in ("X", "i", "C", "M") for ev in events)
    req_spans = {ev["args"]["rid"]: ev for ev in events
                 if ev["ph"] == "X" and ev["name"] == "request"}
    assert sorted(req_spans) == [0, 1, 2]
    for rid, span in req_spans.items():
        assert span["pid"] == REQUEST_PID and span["tid"] == rid
        t0, t1 = span["ts"], span["ts"] + span["dur"]
        children = [ev for ev in events
                    if ev["ph"] == "X" and ev["pid"] == REQUEST_PID
                    and ev["tid"] == rid and ev["name"] != "request"]
        # every per-request child span nests inside its request span
        # (Chrome nests same-thread slices by interval containment);
        # decode count = tokens after the prefill-carried first one
        assert children
        eps = 1e-3                       # us rounding slack
        for ch in children:
            assert ch["ts"] >= t0 - eps
            assert ch["ts"] + ch["dur"] <= t1 + eps
        n_dec = sum(ch["name"] == "decode" for ch in children)
        assert n_dec == len(reqs[rid].tokens) - 1
        # span args carry exact seconds: decode children sum to the
        # request's recorded decode latencies (token_lat[0] is the final
        # prefill chunk, which carries the first token)
        dec_sum = sum(ch["args"]["dur_s"] for ch in children
                      if ch["name"] == "decode")
        assert dec_sum == pytest.approx(sum(reqs[rid].token_lat[1:]),
                                        rel=1e-6)
        pre = [ch for ch in children if ch["name"] == "prefill_chunk"]
        assert len(pre) == -(-len(reqs[rid].prompt) // eng.chunk)
        last_chunk = max(pre, key=lambda ch: ch["ts"])
        assert last_chunk["args"]["dur_s"] == \
            pytest.approx(reqs[rid].token_lat[0], rel=1e-6)
    # engine-track slices + occupancy counter events exist
    assert any(ev["ph"] == "X" and ev["pid"] == ENGINE_PID
               for ev in events)
    assert any(ev["ph"] == "C" and ev["name"] == "occupancy"
               for ev in events)


def test_engine_stats_reconcile_with_meters():
    cfg = _cfg(cim=True)
    params = _params(cfg, cim=True)
    reqs = _requests(cfg, [32, 32], [3, 2])
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=64)
    stats = eng.run(reqs, realtime=False)
    # dispatch accounting: 2 prefill chunks x 32 rows + decode steps x
    # n_slots rows, through every chip of the stack
    assert stats["mvm_dispatches"] == eng.chipmeter.mvm_dispatches()
    assert stats["energy_pj"] == eng.chipmeter.energy_pj()
    assert 0 < stats["utilization"] <= 1
    # per-request attributed energy: useful rows x per-token stack cost
    ptok = eng.chipmeter.per_token_pj()
    for r in reqs:
        assert r.energy_pj == (len(r.prompt) + len(r.tokens) - 1) * ptok
    # registry sees the same trace count the stats report
    assert eng.metrics.value("jit_traces", entry="pool_decode") == \
        stats["decode_traces"] == 1
    assert eng.metrics.value("serve_tokens_generated") == stats["tokens"]
    h = eng.metrics.get("serve_ttft_s")
    assert h.count() == len(reqs)


def test_metrics_do_not_perturb_tokens(tmp_path):
    """The zero-overhead contract, stated as bitwise determinism: a run
    with a shared registry + trace buffer + strict watchdog, its phase
    spans recorded by a running JAX profiler, emits EXACTLY the token ids
    of a bare run over the same request stream."""
    cfg = _cfg()
    params = _params(cfg)
    lens, gens = [32, 64, 32, 32], [4, 2, 3, 5]

    bare = _requests(cfg, lens, gens)
    eng0 = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=96)
    eng0.run(bare, realtime=False)

    metered = _requests(cfg, lens, gens)
    eng1 = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=96,
                                    metrics=MetricsRegistry(),
                                    trace=TraceBuffer(), strict_jit=True)
    with jax.profiler.trace(str(tmp_path)):
        eng1.run(metered, realtime=False)

    for r0, r1 in zip(bare, metered):
        assert r0.tokens == r1.tokens, f"request {r0.rid} diverged"
    # the spans reached the profiler's host plane: one serve.iter event
    # per loop iteration, one dispatch event per call (warm-up included)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("serve.")]
    phase = eng1.metrics.get("serve_phase_s")
    assert names.count("serve.iter") == phase.count(phase="serve.iter") > 0
    assert names.count("serve.dispatch.pool_decode") == \
        eng1.jitwatch.entries["pool_decode"].calls


MAX_LEN = 4096


def _phase_run():
    """A traced CIM engine run whose first decode step also collects
    garbage (and no other step does); returns (engine, chrome events, decode step seconds as
    serve_decode_step_s observed them)."""
    cfg = _cfg(cim=True)
    params = _params(cfg, cim=True)
    trace = TraceBuffer()
    # a long pool makes each decode step take milliseconds on the CPU, so
    # the spans' own microseconds stay well under 1% of it
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=MAX_LEN,
                                   trace=trace)
    steps = []
    observe = eng._h_decode.observe

    def observe_step(v, **labels):
        steps.append(v)
        observe(v, **labels)
    eng._h_decode.observe = observe_step
    decode = eng._decode_once
    collected = []

    def decode_collecting(now):
        if not collected:
            collected.append(gc.collect())
        return decode(now)
    eng._decode_once = decode_collecting
    # no automatic collections: one landing between a step's dispatch and
    # wait spans would leave more than 1% of a CPU step uncovered
    gc.disable()
    try:
        eng.run(_requests(cfg, [32, 64, 32], [3, 2, 1]), realtime=False)
    finally:
        gc.enable()
    return eng, trace.to_dict()["traceEvents"], steps


def _slices(events, name):
    return sorted(((ev["ts"], ev["ts"] + ev["dur"], ev) for ev in events
                   if ev["ph"] == "X" and ev["pid"] == ENGINE_PID
                   and ev["name"] == name), key=lambda t: t[:2])


def test_engine_phase_spans_nest_in_iterations():
    """Every loop iteration is one serve.iter slice, the parent of the
    phases inside it; each decode step's dispatch and wait spans lie in
    the interval serve_decode_step_s timed and cover at least 99% of it;
    request phases carry their rid; the gc callback is gone after run."""
    eng, events, steps = _phase_run()
    assert check_obs.check_trace_schema({"traceEvents": events,
                                         "displayTimeUnit": "ms"})
    assert check_obs.check_phase_nesting({"traceEvents": events}) > 0
    iters = _slices(events, "serve.iter")
    phase = eng.metrics.get("serve_phase_s")
    assert len(iters) == phase.count(phase="serve.iter")
    assert len(_slices(events, "serve.schedule")) == len(iters)
    names = {ev["name"] for ev in events
             if ev["ph"] == "X" and ev["pid"] == ENGINE_PID}
    assert names == {
        "serve.iter", "serve.schedule", "serve.admit", "serve.prefill",
        "serve.prefill.wait", "serve.prefill.readback", "serve.decode",
        "serve.decode.wait", "serve.decode.readback", "serve.decode.emit",
        "serve.finish", "serve.gc", "serve.dispatch.slot_reset",
        "serve.dispatch.slot_prefill", "serve.dispatch.pool_decode",
        "serve.dispatch.slot_activate"}
    # the forced collection ran inside an iteration
    ((g0, g1, gc_ev),) = _slices(events, "serve.gc")
    assert gc_ev["args"]["generation"] == 2
    assert any(s <= g0 and g1 <= e for s, e, _ in iters)
    for name in ("serve.admit", "serve.prefill", "serve.finish"):
        assert all("rid" in ev["args"] for _, _, ev in _slices(events, name))
    decodes = _slices(events, "serve.decode")
    dispatches = _slices(events, "serve.dispatch.pool_decode")
    waits = _slices(events, "serve.decode.wait")
    assert len(decodes) == len(dispatches) == len(waits) == len(steps) > 0
    for (d0, d1, dec), (p0, p1, _), (w0, w1, _), dt in zip(
            decodes, dispatches, waits, steps):
        assert dec["args"]["live"] >= 1
        assert d0 <= p0 <= p1 <= w0 <= w1 <= d1
        dt_us = dt * 1e6
        assert w1 - p0 <= dt_us + 1e-3
        assert (p1 - p0) + (w1 - w0) >= 0.99 * dt_us
    assert not any(getattr(cb, "__self__", None) is eng.tracer
                   for cb in gc.callbacks)


def test_check_obs_refuses_a_phase_outside_iterations():
    doc = {"traceEvents": [
        {"ph": "X", "name": "serve.iter", "pid": 1, "tid": 0, "ts": 0.0,
         "dur": 10.0, "args": {"dur_s": 1e-5}},
        {"ph": "X", "name": "serve.decode", "pid": 1, "tid": 0, "ts": 2.0,
         "dur": 5.0, "args": {"dur_s": 5e-6}},
        {"ph": "X", "name": "serve.gc", "pid": 1, "tid": 0, "ts": 10.5,
         "dur": 1.0, "args": {"dur_s": 1e-6}}]}
    assert check_obs.check_phase_nesting(doc) == 1
    doc["traceEvents"].append(
        {"ph": "X", "name": "serve.finish", "pid": 1, "tid": 0, "ts": 9.0,
         "dur": 2.0, "args": {"dur_s": 2e-6}})
    with pytest.raises(check_obs.CheckError, match="serve.finish"):
        check_obs.check_phase_nesting(doc)


# ------------------------------------------- multi-process export/merge

def test_export_extra_labels_stamp_every_series():
    """extra_labels (serve's {"rank": N}) land on every exported series
    in both formats; instruments stay rank-unaware; a collision with an
    instrument's own label raises instead of silently relabeling."""
    reg = MetricsRegistry()
    reg.counter("serve_tokens").inc(3, slot="0")
    reg.gauge("pool_occupancy").set(2.0)
    reg.histogram("token_ms").observe(1e-3)
    doc = reg.to_dict(extra_labels={"rank": "1"})
    for fam in ("counters", "gauges", "histograms"):
        for s in doc[fam]:
            assert s["labels"]["rank"] == "1", (fam, s)
    assert doc["counters"][0]["labels"]["slot"] == "0"  # own labels kept
    assert 'rank="1"' in reg.to_prometheus(extra_labels={"rank": "1"})
    # no extra_labels -> byte-identical single-process export
    assert reg.to_dict() == reg.to_dict(extra_labels=None)
    with pytest.raises(ValueError):
        reg.to_dict(extra_labels={"slot": "9"})


def test_merge_registries_and_collision():
    """Rank-labeled docs merge into one; the SAME series identity
    appearing twice is double-counting and must raise."""
    from repro.obs import merge_registries
    docs = []
    for rank in range(2):
        reg = MetricsRegistry()
        reg.counter("serve_tokens").inc(10 * (rank + 1))
        reg.histogram("token_ms").observe(1e-3 * (rank + 1))
        docs.append(reg.to_dict(extra_labels={"rank": str(rank)}))
    m = merge_registries(docs)
    assert len(m["counters"]) == 2
    ranks = sorted(s["labels"]["rank"] for s in m["counters"])
    assert ranks == ["0", "1"]
    assert sum(s["value"] for s in m["counters"]) == 30
    assert len(m["histograms"]) == 2
    # unlabeled duplicate identity: double-counting
    reg = MetricsRegistry()
    reg.counter("serve_tokens").inc(1)
    with pytest.raises(ValueError):
        merge_registries([reg.to_dict(), reg.to_dict()])


def test_dict_to_prometheus_renders_merged_doc():
    from repro.obs import dict_to_prometheus, merge_registries
    docs = []
    for rank in range(2):
        reg = MetricsRegistry()
        reg.counter("serve_tokens").inc(5)
        reg.histogram("token_ms").observe(2e-3)
        docs.append(reg.to_dict(extra_labels={"rank": str(rank)}))
    text = dict_to_prometheus(merge_registries(docs))
    assert text.count("# TYPE serve_tokens counter") == 1   # one per family
    assert text.count("# TYPE token_ms histogram") == 1
    assert 'serve_tokens{rank="0"} 5' in text
    assert 'serve_tokens{rank="1"} 5' in text
    assert 'le="+Inf"' in text
    for rank in range(2):
        assert f'token_ms_count{{rank="{rank}"}} 1' in text
        assert f'token_ms_sum{{rank="{rank}"}} 0.002' in text

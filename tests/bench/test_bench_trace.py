"""The reduction from a device trace to the benchmark's numbers
(bench/devtrace.py), on hand-made events and on a trimmed trace recorded
on the chip (data/trace_small_rwkv.json)."""
import pytest

from bench import devtrace


def test_union_busy_and_gaps_by_hand():
    evs = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 20.0, 5.0)]
    assert devtrace.union((s, s + d) for _, s, d in evs) == [(0, 15), (20, 25)]
    assert devtrace.busy_ns(evs) == 20.0
    host = [("bench.window", 0.0, 40.0)]
    program = [("serve.iter", 14.0, 8.0), ("serve.decode.readback", 14.0,
                                           5.0)]
    gaps = devtrace.idle_gaps(evs, program, (0.0, 40.0))
    assert [g[0] for g in gaps] == [devtrace.NO_SPAN,
                                    "serve.decode.readback"]
    assert [g[1] for g in gaps] == pytest.approx([15e-9, 5e-9])
    # device times read 3 ns early: the second gap moves under serve.iter
    gaps = devtrace.idle_gaps(evs, program, (0.0, 40.0), offset=3.0)
    assert [g[0] for g in gaps] == [devtrace.NO_SPAN, "serve.iter"]
    assert devtrace.top_ops(evs) == [["a", 10e-9], ["b", 10e-9],
                                     ["c", 5e-9]]
    assert devtrace.kernel_ns(evs, "^[ab]$") == (20.0, 2)
    assert devtrace.in_window(evs, (7.0, 22.0)) == [
        ("a", 7.0, 3.0), ("b", 7.0, 8.0), ("c", 20.0, 2.0)]
    assert devtrace.window_of(host) == (0.0, 40.0)


def test_op_names_and_wrappers():
    text = ("%cim_mvm_packed_pallas.48 = f32[32,4096]{1,0:T(8,128)S(1)} "
            "custom-call(s32[1680]{0:T(1024)S(1)} %copy.1)")
    assert devtrace.op_name(text) == "cim_mvm_packed_pallas.48"
    assert devtrace.op_name("fusion.3") == "fusion.3"
    evs = [("while.2", 0.0, 30.0), ("cim_mvm_packed_pallas.48", 0.0, 10.0),
           ("fusion.37", 12.0, 5.0)]
    assert devtrace.top_ops(evs) == [["cim_mvm_packed_pallas.48", 10e-9],
                                     ["fusion.37", 5e-9]]
    assert devtrace.busy_ns(evs) == 30.0
    assert devtrace.kernel_ns(evs, r"^cim_mvm_packed_pallas") == (10.0, 1)


def test_reduction_on_a_trimmed_chip_trace():
    """60 ms of a traced rwkv6-7b-L2.batch-decode window on a TPU v5e
    (bench/run.py --trace 1, reduced by devtrace.load): every whole decode
    step holds one packed CIM kernel event per projection and layer."""
    import json
    import os
    import re
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_small_rwkv.json")
    with open(path) as f:
        small = json.load(f)
    w = tuple(small["window"])
    evs = devtrace.in_window([tuple(e) for e in small["device"]], w)
    # the spans the benchmark recorded around the engine's calls then
    host = [tuple(h) for h in small["host"]]
    calls = [h for h in host if h[0] != devtrace.WINDOW_SPAN]
    assert all(" = " not in n for n, _, _ in evs)
    busy = devtrace.busy_ns(evs)
    assert 0.5 * (w[1] - w[0]) < busy < w[1] - w[0]
    assert all(not devtrace.WRAPPERS.match(n)
               for n, _ in devtrace.top_ops(evs))
    gaps = devtrace.idle_gaps(evs, calls, w)
    assert gaps and all(g[0].startswith("bench.engine.") for g in gaps)
    assert sum(g[1] for g in devtrace.idle_gaps(evs, calls, w, k=10**6)) \
        == pytest.approx((w[1] - w[0] - busy) * 1e-9)
    steps = [(s, s + d) for n, s, d in host
             if n == "bench.engine.decode_step" and w[0] <= s
             and s + d <= w[1]]
    assert len(steps) == 3
    for s, e in steps:
        inside = [x for x in evs if s <= x[1] <= e]
        t_ns, n = devtrace.kernel_ns(inside, r"^cim_mvm_packed_pallas")
        assert n == 8 * 2 and 0 < t_ns < e - s

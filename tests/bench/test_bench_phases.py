"""The program's phase spans in a device trace (bench/phases.py) and the
readers built on them: clock alignment, idle attribution and
host_ms_per_step, on hand-made events and on a trimmed trace recorded on
the chip (data/trace_small_rwkv_phases.json)."""
import dataclasses
import json
import os
from typing import Optional

import pytest

from bench import devtrace, harness, phases
from repro.obs import MetricsRegistry

OFFSET = 1000.0          # device events read this many ns early

# two loop iterations and a collection after them, on the host's clock
PROGRAM = [
    ("serve.iter", 0.0, 100.0),
    ("serve.schedule", 0.0, 8.0),
    ("serve.dispatch.pool_decode", 10.0, 10.0),
    ("serve.decode.wait", 20.0, 40.0),
    ("serve.decode.readback", 60.0, 20.0),
    ("serve.decode.emit", 80.0, 10.0),
    ("serve.iter", 100.0, 100.0),
    ("serve.dispatch.slot_prefill", 105.0, 10.0),
    ("serve.prefill.wait", 115.0, 35.0),
    ("serve.prefill.readback", 150.0, 10.0),
    ("serve.dispatch.pool_decode", 160.0, 10.0),
    ("serve.decode.wait", 170.0, 20.0),
    ("serve.sleep", 190.0, 5.0),
    ("serve.gc", 202.0, 2.0),
]
# what ran on the device, on the host's clock: each step's two kernel
# events, and the readback's copy after the first step
BUSY = [("cim_mvm_packed_pallas.1", 10.0, 15.0),
        ("cim_mvm_packed_pallas.2", 30.0, 20.0),
        ("copy.1", 60.0, 2.0),
        ("cim_mvm_packed_pallas.1", 120.0, 10.0),
        ("cim_mvm_packed_pallas.2", 131.0, 19.0),
        ("cim_mvm_packed_pallas.1", 172.0, 8.0),
        ("cim_mvm_packed_pallas.2", 181.0, 4.0)]
# the program runs: three packed steps and the readback's slice. The first
# step starts at its dispatch and the second ends at its wait's end, so
# only OFFSET fits.
MODULES = [("jit_step(1)", 10.0, 40.0), ("jit_dynamic_slice(2)", 60.0, 2.0),
           ("jit_chunk_step(3)", 120.0, 30.0), ("jit_step(1)", 172.0, 13.0)]
WINDOW = (0.0 - OFFSET, 210.0 - OFFSET)     # on the device's clock


def _device(events, shift=OFFSET):
    return [(n, s - shift, d) for n, s, d in events]


def _runs(modules=MODULES, shift=OFFSET):
    return phases.step_runs(_device(modules, shift), _device(BUSY, shift))


def test_align_recovers_a_planted_offset():
    assert phases.step_intervals(PROGRAM) == [(10.0, 60.0), (105.0, 150.0),
                                              (160.0, 190.0)]
    assert _runs() == [(10.0 - OFFSET, 50.0 - OFFSET),
                       (120.0 - OFFSET, 150.0 - OFFSET),
                       (172.0 - OFFSET, 185.0 - OFFSET)]
    assert phases.align(PROGRAM, _runs()) == (OFFSET, 0.0)
    for shift in (0.0, -2.5e6, 7.25e5):
        assert phases.align(PROGRAM, _runs(shift=shift)) == (shift, 0.0)


def test_align_refuses_what_no_offset_fits():
    late = MODULES[:-1] + [("jit_step(1)", 172.0, 23.0)]
    assert phases.align(PROGRAM, _runs(late)) is None
    assert phases.idle_host_pct(_device(BUSY), _device(late), PROGRAM,
                                WINDOW) == (None, None)
    # a run short of one per step
    assert phases.align(PROGRAM, _runs(MODULES[1:])) is None
    assert phases.align([], _runs()) is None


def test_innermost_pieces():
    spans = [("a", 0.0, 10.0), ("b", 2.0, 3.0), ("c", 5.0, 5.0),
             ("d", 6.0, 1.0), ("e", 12.0, 1.0)]
    assert devtrace.innermost(spans) == [
        (0.0, 2.0, "a"), (2.0, 5.0, "b"), (5.0, 6.0, "c"), (6.0, 7.0, "d"),
        (7.0, 10.0, "c"), (12.0, 13.0, "e")]


def test_idle_by_phase_and_idle_host_pct_by_hand():
    by = phases.idle_by_phase(_device(BUSY), PROGRAM, WINDOW, OFFSET)
    assert by == pytest.approx({
        "serve.schedule": 8.0, "serve.iter": 2.0 + 10.0 + 5.0 + 5.0,
        "serve.decode.wait": 5.0 + 10.0 + 2.0 + 1.0 + 5.0,
        "serve.decode.readback": 18.0, "serve.decode.emit": 10.0,
        "serve.dispatch.slot_prefill": 10.0,
        "serve.prefill.wait": 5.0 + 1.0,
        "serve.prefill.readback": 10.0, "serve.dispatch.pool_decode": 10.0,
        "serve.sleep": 5.0, "serve.gc": 2.0, phases.UNATTRIBUTED: 8.0})
    idle = 210.0 - sum(d for _, _, d in BUSY)
    assert sum(by.values()) == pytest.approx(idle)
    pct, found = phases.idle_host_pct(_device(BUSY), _device(MODULES),
                                      PROGRAM, WINDOW)
    assert found == (OFFSET, 0.0)
    host = 8.0 + 22.0 + 18.0 + 10.0 + 10.0 + 10.0 + 10.0 + 2.0
    assert pct == pytest.approx(100.0 * host / 210.0)
    assert pct < 100.0 * idle / 210.0
    assert [phases.is_host_phase(n) for n in (
        "serve.iter", "serve.gc", "serve.decode.wait", "serve.sleep",
        phases.UNATTRIBUTED)] == [True, True, False, False, False]


@dataclasses.dataclass
class _Ctx:
    """The fields of harness.Context that the two readers read."""
    registry: MetricsRegistry
    trace: Optional[dict] = None


def _registry(program):
    """A registry holding what the engine's Tracer would have observed
    for these spans."""
    reg = MetricsRegistry()
    h = reg.histogram("serve_phase_s")
    for n, _, d in program:
        h.observe(d * 1e-9, phase=n)
    return reg


def test_host_ms_per_step_reader():
    read = harness.reader("host_ms_per_step")
    # iterations 200 ns, waits 95 ns, sleep 5 ns: 100 ns held over 2
    assert read(_Ctx(_registry(PROGRAM))) == pytest.approx(50.0 * 1e-6)
    # a program without phase spans (or a run without iterations): nothing
    assert read(_Ctx(MetricsRegistry())) is None
    assert read(_Ctx(_registry(PROGRAM[1:2]))) is None


def test_idle_host_pct_reader(capsys):
    read = harness.reader("idle_host_pct")
    plane = "/device:TPU:0"
    trace = {"device": {plane: _device(BUSY)}, "window": WINDOW,
             "modules": {plane: _device(MODULES)}, "program": PROGRAM}
    assert read(_Ctx(MetricsRegistry(), trace)) == pytest.approx(
        phases.idle_host_pct(_device(BUSY), _device(MODULES), PROGRAM,
                             WINDOW)[0])
    assert "program clock offset 0.001 ms, interval width 0.0 ms" in \
        capsys.readouterr().err
    # what devtrace.load() gives without the program's spans and runs:
    # nothing
    for key in ("program", "modules"):
        assert read(_Ctx(MetricsRegistry(), {
            k: v for k, v in trace.items() if k != key})) is None
    assert read(_Ctx(MetricsRegistry(), None)) is None


# ------------------------------------------------- a trimmed chip trace

def _xspace(planes: dict) -> bytes:
    """A serialized XSpace of {plane: {line: {"timestamp_ns", "events":
    [[name, offset_ps, duration_ps], ...]}}}."""
    from jax.profiler import ProfileData
    text = []
    for pid, (pname, lines) in enumerate(sorted(planes.items()), 1):
        ids: dict = {}
        body = []
        for lid, (lname, line) in enumerate(sorted(lines.items()), 1):
            evs = " ".join(
                f"events {{ metadata_id: {ids.setdefault(n, len(ids) + 1)} "
                f"offset_ps: {off} duration_ps: {dur} }}"
                for n, off, dur in line["events"])
            body.append(f"lines {{ id: {lid} name: {json.dumps(lname)} "
                        f"timestamp_ns: {line['timestamp_ns']} {evs} }}")
        meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                        f"name: {json.dumps(n)} }} }}"
                        for n, i in ids.items())
        text.append(f"planes {{ id: {pid} name: {json.dumps(pname)} "
                    f"{' '.join(body)} {meta} }}")
    return ProfileData.text_proto_to_serialized_xspace(" ".join(text))


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """72 ms of a traced rwkv6-7b-L2.batch-decode window on a TPU v5e (five
    packed steps, the chip's own events and the program's spans), written
    back as a profiler log directory, and its two reductions."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_small_rwkv_phases.json")) as f:
        data = json.load(f)
    log_dir = tmp_path_factory.mktemp("trace")
    run = log_dir / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_xspace(data["planes"]))
    return data, devtrace.load(str(log_dir))


def test_load_on_a_trimmed_chip_trace(small):
    """devtrace.load() keeps the device's ops and program runs, the
    benchmark's own spans and the program's `serve.*` spans apart."""
    data, tr = small
    assert set(tr) == {"device", "modules", "host", "program", "lines"}
    (plane,) = tr["device"]
    recorded = data["planes"][plane][devtrace.OPS_LINE]
    assert [e[0] for e in tr["device"][plane]] == \
        [n for n, _, _ in sorted(recorded["events"], key=lambda e: e[1])]
    assert [e[0] for e in tr["modules"][plane]] == [
        n for n, _, _ in sorted(data["planes"][plane][
            devtrace.MODULES_LINE]["events"], key=lambda e: e[1])]
    assert tr["lines"] == {plane: sorted(data["planes"][plane])}
    assert tr["host"] and all(n.startswith(devtrace.SPAN_PREFIX)
                              for n, _, _ in tr["host"])
    names = [n for n, _, _ in tr["program"]]
    assert all(n.startswith(devtrace.PROGRAM_PREFIX) for n in names)
    assert [s for _, s, _ in tr["program"]] == \
        sorted(s for _, s, _ in tr["program"])
    assert {"serve.iter", "serve.schedule", "serve.prefill",
            "serve.dispatch.slot_prefill", "serve.prefill.wait",
            "serve.decode", "serve.dispatch.pool_decode",
            "serve.decode.wait", "serve.decode.readback",
            "serve.decode.emit"} <= set(names)


def test_phases_on_a_trimmed_chip_trace(small):
    """On the chip's own events: one offset fits every step (device
    events read early), a planted shift moves it by exactly as much, the
    host's share of the idle time is part of the device's idle share, and
    host_ms_per_step reads the held iterations."""
    data, tr = small
    dev, prog = devtrace.device_events(tr), tr["program"]
    mods = phases.device_modules(tr)
    w = tuple(data["window"])
    runs = phases.step_runs(mods, dev)
    assert len(runs) == len(phases.step_intervals(prog)) > 2
    off, width = phases.align(prog, runs)
    assert 0.0 < off < 5e6 and width > 0.0
    # the whole window's steps fit a narrower interval inside this one
    assert abs(data["offset_ns"] - off) <= width / 2
    for shift in (-1.5e6, 2.5e5):
        moved = [(s + shift, e + shift) for s, e in runs]
        assert phases.align(prog, moved) == \
            pytest.approx((off - shift, width), abs=1e-3)
    pct, _ = phases.idle_host_pct(dev, mods, prog, w)
    idle = 100.0 * (1.0 - devtrace.busy_ns(devtrace.in_window(dev, w))
                    / (w[1] - w[0]))
    assert 0.0 < pct <= idle
    iters = [d for n, _, d in prog if n == "serve.iter"]
    held = sum(iters) - sum(d for n, _, d in prog
                            if n in ("serve.decode.wait",
                                     "serve.prefill.wait", "serve.sleep"))
    got = harness.reader("host_ms_per_step")(_Ctx(_registry(prog)))
    assert got == pytest.approx(held / len(iters) * 1e-6)
    assert 0.5 < got < 10.0


def test_idle_gaps_on_a_trimmed_chip_trace(small):
    """The breakdown's idle gaps are named by the program's own spans,
    with device times on the spans' clock (`phases.align`): on the chip's
    trace the device idles through the decode readbacks, and every idle
    nanosecond is in some gap."""
    data, tr = small
    dev, prog = devtrace.device_events(tr), tr["program"]
    w = tuple(data["window"])
    evs = devtrace.in_window(dev, w)
    off, _ = phases.align(prog, phases.step_runs(phases.device_modules(tr),
                                                 dev))
    gaps = devtrace.idle_gaps(evs, prog, w, off)
    names = {n for n, _ in gaps}
    assert names <= {n for n, _, _ in prog} | {devtrace.NO_SPAN}
    assert "serve.decode.readback" in names
    idle = (w[1] - w[0] - devtrace.busy_ns(evs)) * 1e-9
    assert sum(d for _, d in devtrace.idle_gaps(evs, prog, w, off,
                                                k=10**6)) == \
        pytest.approx(idle)

"""One run of one cell of BENCHMARK.json: set-up, the measured window, the
metrics, and the comparison with the plain reference that decides
`correct`.

Everything that belongs to one configuration, traffic mix, cell check or
metric is a file found by name:

  bench/configs/<config>.json   sizes, CIM operating point, source, cuts
  bench/families/<family>.py    a configuration's `family`: arch_kw,
                                weights, layers (the reference's), the
                                packed calls of a step, model FLOPs
  bench/mixes/<mix>.json        traffic parameters (bench/traffic.py)
  bench/checks/<cell>.json      limits of the numbers `correct` compares
  bench/metrics/<metric>.py     read(ctx) -> float | None, one per metric

The system under test is the program's normal serving path: weights (made
here from the seed) go through `arch_serving(cfg).deploy_cim` and
`core.verify.verify_deployed`, the continuous-batching engine warms up its
own shapes, and `ContinuousBatchingEngine.run` is the window.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .families import family, load

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """BENCHMARK.json and the files it names."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.spec = _json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        return _json(os.path.join(BENCH_DIR, "mixes", name + ".json"))

    def check(self, cell: str) -> dict:
        return _json(os.path.join(BENCH_DIR, "checks", cell + ".json"))

    def metrics(self, cell: str, traced: bool) -> List[dict]:
        """The cell's end-to-end metrics (untraced run) or per-layer
        metrics (traced run)."""
        group = self.spec["per_layer" if traced else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device {device_kind!r} is not in bench/peaks.json")
    return table["devices"][device_kind]


def reader(name: str) -> Callable:
    return load(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                f"bench_metric_{name}").read


@dataclasses.dataclass
class Context:
    """What a metric reader may read. Times in seconds."""
    config: dict
    mix: dict
    cell: str
    requests: list            # engine Requests after the window
    stats: dict               # ContinuousBatchingEngine.run's summary
    registry: Any             # the engine's MetricsRegistry
    setup_s: float
    deploy_s: float
    window_s: float           # first admission to last completion
    trace: Optional[dict] = None       # devtrace.load() + "window"
    peaks: Optional[dict] = None


def arch_config(config: dict, mesh):
    """The program's ArchConfig for a configuration file (sizes from the
    file through its family's `arch_kw`, family flags from the program's
    registry)."""
    import jax.numpy as jnp
    from repro import configs
    cim = config["cim"]
    return configs.get(config["arch"]).replace(
        cim_mode="packed", dtype=jnp.float32, cim_in_bits=cim["in_bits"],
        cim_out_bits=cim["out_bits"], cim_mesh=mesh,
        **family(config).arch_kw(config))


def _check_layout(made, expect) -> None:
    import jax
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), made)
    want = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), expect)
    if got != want:
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter layout:\n{got}\nvs\n{want}")


def _sample(requests, check: dict, seed: int) -> list:
    """The requests the reference replays: the one with most served tokens
    and a seeded draw of the others."""
    from .traffic import seed_words
    done = [r for r in requests if r.t_done >= 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.Generator(np.random.PCG64(seed_words(seed, 6)[5]))
    k = min(check["requests"] - 1, len(rest))
    pick = list(rng.choice(len(rest), size=k, replace=False)) if k else []
    return [longest] + [rest[i] for i in sorted(pick)]


def token_gaps(config: dict, params, deploy_key, replay: list,
               precision: str = "highest",
               against: Optional[List[np.ndarray]] = None):
    """Per served token, the gap by which its logit lies below the
    reference's best, in units of the standard deviation of the
    reference's logits at that position, for every request of `replay`
    [(prompt, tokens)].

    With `against` (the reference's logits from a highest-precision pass),
    this pass's logits pick the token instead: the control's reading.
    Returns (gaps, one array per request; logits of this pass)."""
    from . import reference
    seqs = [np.concatenate([p, np.asarray(t[:-1], np.int32)])
            for p, t in replay]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(t)) for p, t in replay]
    got = reference.logits(config, params, deploy_key, seqs, rows,
                           precision=precision)
    gaps = []
    for i, (p, t) in enumerate(replay):
        ref = got[i] if against is None else against[i]
        pick = np.asarray(t) if against is None else got[i].argmax(-1)
        gaps.append((ref.max(-1) - ref[np.arange(len(pick)), pick])
                    / ref.std(-1))
    return gaps, got


def keys(seed: int):
    """(weights key, deploy key) of a seed."""
    import jax
    from .traffic import seed_words
    w = seed_words(seed, 2)
    return jax.random.PRNGKey(w[0]), jax.random.PRNGKey(w[1])


def deploy(config: dict, params, deploy_key, mesh):
    """The program's deploy: CIM compile of every projection, verified.
    Returns (deployed params, cfg, seconds)."""
    import jax
    from repro.core.types import CoreSpec
    from repro.core.verify import verify_deployed
    from repro.launch.steps import arch_serving
    cim = config["cim"]
    cfg = arch_config(config, mesh)
    sv = arch_serving(cfg)
    _check_layout(params, jax.eval_shape(sv.init_params,
                                         jax.random.PRNGKey(0)))
    spec = CoreSpec(rows=cim["core_rows"], cols=cim["core_cols"],
                    n_cores=cim["n_cores"])
    t0 = time.perf_counter()
    params = verify_deployed(sv.deploy_cim(
        deploy_key, params, mode=cim["programming"],
        in_alpha=cim["in_alpha"], spec=spec))
    jax.block_until_ready(params)
    return params, cfg, time.perf_counter() - t0


def serve_window(config: dict, mix: dict, planned, params, cfg, mesh, *,
                 traced: bool, hook: Optional[Callable] = None,
                 t_start: Optional[float] = None):
    """Engine set-up and the measured window. Returns (engine, requests,
    stats, setup_s, window_s, trace)."""
    from repro.launch.scheduler import ContinuousBatchingEngine, Request
    from . import devtrace, traffic
    engine = ContinuousBatchingEngine(
        cfg, params, n_slots=mix["slots"], max_len=mix["max_len"],
        chunk=mix["chunk"], mesh=mesh, strict_jit=True)
    if hook is not None:
        hook(engine)
    engine.warmup(traffic.chunk_lengths(planned, mix["chunk"]))
    engine.jitwatch.seal()
    requests = [Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new,
                        arrival=p.arrival) for p in planned]
    setup_s = time.perf_counter() - t_start if t_start is not None else 0.0
    trace = None
    if traced:
        import jax
        with devtrace.capture() as trace:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
                stats = engine.run(requests, warm=False)
        trace["window"] = devtrace.window_of(trace["host"])
    else:
        stats = engine.run(requests, warm=False)
    done = [r for r in requests if r.t_done >= 0]
    window_s = (max(r.t_done for r in done)
                - min(r.t_admit for r in done)) if done else 0.0
    return engine, requests, stats, setup_s, window_s, trace


def memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(bench: Bench, cell_name: str, seed: int, seconds: float,
             traced: bool, *, t_start: float,
             hook: Optional[Callable] = None,
             config: Optional[dict] = None, mix: Optional[dict] = None,
             check: Optional[dict] = None,
             metric_list: Optional[List[dict]] = None) -> dict:
    """One run; returns the result line as a dict (`check` last).

    config / mix / check / metric_list default to the files the cell names
    in BENCHMARK.json; tests pass small ones. `hook(engine)` runs before
    warm-up (tests use it to break the timed path)."""
    import jax
    if config is None:
        cell = bench.cell(cell_name)
        config = bench.config(cell["config"])
        mix = mix or bench.mix(cell["traffic"])
    check = check or bench.check(cell_name)
    if metric_list is None:
        metric_list = bench.metrics(cell_name, traced)
    with jax.default_matmul_precision(config["matmul_precision"]):
        return _run(cell_name, seed, seconds, traced, t_start, hook, config,
                    mix, check, metric_list)


def _run(cell_name, seed, seconds, traced, t_start, hook, config, mix, check,
         metric_list) -> dict:
    import jax
    from repro.launch.mesh import serving_mesh
    from . import devtrace, phases, traffic, weights
    wkey, dkey = keys(seed)
    planned = traffic.generate(mix, seed, seconds, config["vocab_size"])
    mesh = serving_mesh()
    params, cfg, deploy_s = deploy(config, weights.make(config, wkey), dkey,
                                   mesh)
    engine, requests, stats, setup_s, window_s, trace = serve_window(
        config, mix, planned, params, cfg, mesh, traced=traced, hook=hook,
        t_start=t_start)
    del params
    peak = memory_peak()
    dev = jax.devices()[0]
    ctx = Context(config=config, mix=mix, cell=cell_name, requests=requests,
                  stats=stats, registry=engine.metrics, setup_s=setup_s,
                  deploy_s=deploy_s, window_s=window_s,
                  trace=trace,
                  peaks=peaks(dev.device_kind) if dev.platform == "tpu"
                  else None)
    metrics = {}
    for m in metric_list:
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out: Dict[str, Any] = {"attempted": len(requests)}
    if trace is not None:
        print(f"bench: trace lines {trace['lines']}, "
              f"{len(trace['program'])} program spans", file=sys.stderr,
              flush=True)
    if trace is not None and trace.get("window"):
        w = trace["window"]
        dev_evs = devtrace.device_events(trace)
        evs = devtrace.in_window(dev_evs, w)
        device["busy_s"] = devtrace.busy_ns(evs) * 1e-9
        device["window_s"] = (w[1] - w[0]) * 1e-9
        found = phases.align(trace["program"], phases.step_runs(
            phases.device_modules(trace), dev_evs))
        out["breakdown"] = {
            "device_ops": devtrace.top_ops(evs),
            "idle_gaps": devtrace.idle_gaps(evs, trace["program"], w,
                                            found[0] if found else 0.0)}
    replay = [(r.prompt, list(r.tokens))
              for r in _sample(requests, mix["check"], seed)]
    failed = sum(1 for r in requests
                 if r.t_done < 0 or len(r.tokens) != r.max_new)
    # the program's state is freed before the reference runs, so the
    # reference neither sets the memory peak nor competes for memory
    del engine, ctx, trace
    gc.collect()
    print(f"bench: {sum(a.nbytes for a in jax.live_arrays())} bytes live on "
          "the device before the reference", file=sys.stderr, flush=True)
    gaps, _ = token_gaps(config, weights.make(config, wkey), dkey, replay)
    numbers = gap_numbers(gaps)
    print("bench: " + ", ".join(f"{k} {v!r}" for k, v in numbers.items()),
          file=sys.stderr, flush=True)
    compared = {k: {"value": numbers[k], "limit": v}
                for k, v in sorted(check.items())}
    compared["failed_requests"] = {"value": failed, "limit": 0}
    compared["replayed_tokens"] = {"value": numbers["replayed_tokens"],
                                   "limit": "> 0"}
    correct = (all(numbers[k] <= v for k, v in check.items())
               and failed == 0 and numbers["replayed_tokens"] > 0)
    out.update(correct=bool(correct), failed=failed, metrics=metrics,
               device=device)
    out["check"] = compared
    return out


# gap thresholds (in standard deviations of the reference's logits) of
# the `share_gap_over_<t>sd` numbers
GAP_SD = (0.25, 0.5, 1.0)


def gap_numbers(gaps: List[np.ndarray]) -> Dict[str, Any]:
    """The numbers a cell's check file may hold to a limit, over every
    replayed token's gap (`token_gaps`): the widest and the mean gap, the
    share of served tokens that are not the reference's best, and the
    share whose gap exceeds each of GAP_SD."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    out = {"widest_gap": float(g.max()) if g.size else 0.0,
           "mean_gap": float(g.mean()) if g.size else 0.0,
           "share_off_best": float((g > 0).mean()) if g.size else 0.0}
    for t in GAP_SD:
        out[f"share_gap_over_{t}sd"] = float((g > t).mean()) if g.size \
            else 0.0
    out["replayed_tokens"] = int(g.size)
    return out


def print_result(out: dict) -> None:
    """The compared numbers on stderr as its last lines, then the result
    line on stdout."""
    for k, v in out["check"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "check"]
    line = {k: out[k] for k in order if k in out}
    print(json.dumps(line), flush=True)

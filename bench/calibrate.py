"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        --control 3 --seconds 10 --out chiprun_out/cal.json

For each seed: weights from the seed, the program's deploy, a short window
of the cell's own mix at its own load (long enough to finish the mix's
longest requests), then every number a benchmark run may compare
(`harness.gap_numbers`, over the gaps of the served tokens' logits below
the reference's best) over the same sample of served tokens. For the first `--control`
seeds also the control's reading: the reference at the next precision down
("high", three bfloat16 passes) picks the token at each position, and the
same numbers are read against the float32 reference. The benchmark's own runs never
run the control. Limits go into bench/checks/<cell>.json by hand, from
these readings, with the readings in PERF.md.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH_DIR,
                                                       ".jax_cache")


def _stats(gaps, prefix: str) -> dict:
    from bench.harness import gap_numbers
    return {prefix + k: v for k, v in gap_numbers(gaps).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one reading each")
    ap.add_argument("--control", type=int, default=3,
                    help="also read the control on the first N seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no eviction: an environment that caps the cache's size would evict
    # this cell's own programs between its runs
    jax.config.update("jax_compilation_cache_max_size", -1)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    from bench import harness, traffic, weights
    from repro.launch.mesh import serving_mesh
    bench = harness.Bench()
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    mix = bench.mix(cell["traffic"])
    mesh = serving_mesh()
    rows = []
    with jax.default_matmul_precision(config["matmul_precision"]):
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            wkey, dkey = harness.keys(seed)
            planned = traffic.generate(mix, seed, args.seconds,
                                       config["vocab_size"])
            params, cfg, deploy_s = harness.deploy(
                config, weights.make(config, wkey), dkey, mesh)
            engine, requests, stats, _, window_s, _ = harness.serve_window(
                config, mix, planned, params, cfg, mesh, traced=False)
            replay = [(r.prompt, list(r.tokens)) for r in
                      harness._sample(requests, mix["check"], seed)]
            failed = sum(1 for r in requests
                         if r.t_done < 0 or len(r.tokens) != r.max_new)
            tokens = sum(len(r.tokens) for r in requests)
            del params, engine, requests
            gc.collect()
            params = weights.make(config, wkey)
            t1 = time.perf_counter()
            gaps, ref = harness.token_gaps(config, params, dkey, replay)
            ref_s = time.perf_counter() - t1
            row = {"seed": seed, **_stats(gaps, ""), "failed": failed,
                   "deploy_s": deploy_s, "window_s": window_s,
                   "tok_s": tokens / window_s if window_s > 0 else 0.0,
                   "reference_s": ref_s}
            if i < args.control:
                ctl, _ = harness.token_gaps(config, params, dkey, replay,
                                            precision="high", against=ref)
                row.update(_stats(ctl, "control_"))
            del params, ref
            gc.collect()
            row["seed_s"] = time.perf_counter() - t0
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

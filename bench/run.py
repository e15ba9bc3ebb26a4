"""Benchmark entry point: one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on, which must hold a TPU with at least
the chips the cell asks for; otherwise it exits non-zero and prints no
result. The last line of standard output is the result as one JSON
object; the numbers that decided `correct` are also the last lines of
standard error.
"""
import time

T_START = time.perf_counter()   # set-up is counted from process start

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# the repository root, not this directory, heads the import path: the
# benchmark's modules are imported as the `bench` package
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
# a fixed cache path inside the checkout, whatever the environment says:
# only the first run of a cell in a checkout compiles
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH_DIR,
                                                       ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    bench = harness.Bench()
    cell = bench.cell(args.workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no eviction: an environment that caps the cache's size would evict
    # this cell's own programs between its runs
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

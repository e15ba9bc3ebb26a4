"""cim_kernel_roofline: the packed CIM kernel's share of its roofline:
the least time the chip could take for every packed call of the window
(max of operations over peak FLOP/s and bytes over peak HBM bytes/s, per
call, from bench/flops), over the summed device time of the kernel's events
in the trace. Reads nothing where the events do not match the calls the
engine's counters say ran."""
from bench import devtrace, flops
from bench.families import family

# the packed CIM kernel's ops in the device trace (instruction names
# cim_mvm_packed_pallas.<n> on a TPU v5e)
KERNEL = r"^cim_mvm_packed_pallas"


def read(ctx):
    if ctx.trace is None or not ctx.trace.get("window") or not ctx.peaks:
        return None
    evs = devtrace.in_window(devtrace.device_events(ctx.trace),
                             ctx.trace["window"])
    t_ns, n_events = devtrace.kernel_ns(evs, KERNEL)
    steps = int(ctx.registry.value("serve_decode_steps"))
    c = ctx.mix["chunk"]
    chunks = [min(c, len(r.prompt) - s) for r in ctx.requests
              for s in range(0, len(r.prompt), c)]
    per_step = len(family(ctx.config).dispatches(ctx.config))
    if not n_events or n_events != (steps + len(chunks)) * per_step:
        return None
    least = sum(max(ops / ctx.peaks["flops_per_s"],
                    moved / ctx.peaks["hbm_bytes_per_s"])
                for ops, moved in flops.cim_calls(ctx.config, steps,
                                                  ctx.mix["slots"], chunks))
    return 100.0 * least / (t_ns * 1e-9)

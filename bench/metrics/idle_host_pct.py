"""idle_host_pct: share of the traced window in which no op ran on the
device while the innermost program span was a host phase (any `serve.`
span but `*.wait` and `serve.sleep`), in the same points as
device_idle_pct. Reads the program spans and program runs that
devtrace.load() keeps, and nothing where the trace lacks them or no single
clock offset places every packed step's run inside its dispatch-to-wait
interval."""
import sys

from bench import devtrace, phases


def read(ctx):
    if ctx.trace is None or not ctx.trace.get("window") \
            or not ctx.trace.get("program") or "modules" not in ctx.trace:
        return None
    pct, found = phases.idle_host_pct(
        devtrace.device_events(ctx.trace), phases.device_modules(ctx.trace),
        ctx.trace["program"], ctx.trace["window"])
    print("bench: program clock offset " + (
        f"{found[0] * 1e-6!r} ms, interval width {found[1] * 1e-6!r} ms"
        if found else "not found: no single offset fits every step"),
        file=sys.stderr, flush=True)
    return pct

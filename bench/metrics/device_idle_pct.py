"""device_idle_pct: share of the traced window in which no operation ran
on the device: 1 - (union of op intervals) / window."""
from bench import devtrace


def read(ctx):
    if ctx.trace is None or not ctx.trace.get("window"):
        return None
    w0, w1 = ctx.trace["window"]
    evs = devtrace.in_window(devtrace.device_events(ctx.trace), (w0, w1))
    if not evs:
        return None
    return 100.0 * (1.0 - devtrace.busy_ns(evs) / (w1 - w0))

"""slot_rows_useful_pct: token rows that reached a request over rows pushed
through the chips (the engine's `utilization`: a pool decode step runs
every slot, occupied or not)."""


def read(ctx):
    u = ctx.stats.get("utilization")
    return 100.0 * u if u else None

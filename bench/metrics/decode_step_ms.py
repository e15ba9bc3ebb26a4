"""decode_step_ms: mean pool decode step over the window, from the engine's
`serve_decode_step_s` histogram (host clock around block_until_ready,
exact count and sum)."""


def read(ctx):
    h = ctx.registry.get("serve_decode_step_s")
    n = h.count()
    return h.sum() / n * 1e3 if n else None

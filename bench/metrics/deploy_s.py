"""deploy_s: the chip compiler's share of set-up: host stopwatch around
`deploy_cim` and `verify_deployed`, ended by block_until_ready."""


def read(ctx):
    return ctx.deploy_s

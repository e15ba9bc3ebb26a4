"""setup_s: process start to the first admission of the window (import,
backend, weights from the seed, CIM deploy, pool, warm-up of the cell's
own shapes from the compile cache), host clock."""


def read(ctx):
    return ctx.setup_s

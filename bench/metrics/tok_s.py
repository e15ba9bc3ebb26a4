"""tok_s: output tokens completed over the whole window, first admission
to last completion, host clock."""


def read(ctx):
    tokens = sum(len(r.tokens) for r in ctx.requests)
    return tokens / ctx.window_s if ctx.window_s > 0 else None

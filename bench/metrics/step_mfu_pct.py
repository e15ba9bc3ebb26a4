"""step_mfu_pct: model FLOPs of the work the window completed (the
family's `request_flops`, bench/flops's convention: 2 x matmul parameters
per useful row, the LM head where its logits are used, the family's mixing
such as attention over each row's KV length), over the traced window times
the chip's peak FLOP/s (bench/peaks.json)."""
from bench.families import family


def read(ctx):
    if ctx.trace is None or not ctx.trace.get("window") or not ctx.peaks:
        return None
    w0, w1 = ctx.trace["window"]
    fam = family(ctx.config)
    work = sum(fam.request_flops(ctx.config, len(r.prompt), len(r.tokens))
               for r in ctx.requests if r.tokens)
    if not work:
        return None
    return 100.0 * work / ((w1 - w0) * 1e-9 * ctx.peaks["flops_per_s"])

"""tpot_p95_ms: 95th percentile over all requests of the decode pace a
client sees, (t_done - arrival - t_first) / (tokens - 1), prefill chunks
interleaved into the decode loop included, host clock."""
import numpy as np


def read(ctx):
    pace = [(r.t_done - r.arrival - r.t_first) / (len(r.tokens) - 1)
            for r in ctx.requests if r.t_done >= 0 and len(r.tokens) > 1]
    return float(np.percentile(pace, 95) * 1e3) if pace else None

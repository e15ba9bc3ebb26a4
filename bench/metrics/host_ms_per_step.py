"""host_ms_per_step: mean host time per engine-loop iteration during which
the chip was not holding the host: each `serve.iter` span's time less its
`*.wait` and `serve.sleep` children, from the engine's `serve_phase_s`
histogram (exact sums and counts). What dispatching ahead could hide."""

BLOCKED = ("serve.decode.wait", "serve.prefill.wait", "serve.sleep")


def read(ctx):
    h = ctx.registry.get("serve_phase_s")
    n = h.count(phase="serve.iter") if h is not None else 0
    if not n:
        return None
    held = h.sum(phase="serve.iter") - sum(h.sum(phase=p) for p in BLOCKED)
    return held / n * 1e3

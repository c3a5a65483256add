"""Share of the roofline reached by the `gru_scan` Pallas kernel.

Kernel time: device events of `gru_scan` in the traced window.  Work: one
kernel forward per refit step (the backward replays the jnp reference) and
one per model recovery, at the unpadded shapes [slots x windows, k, 4].
"""
from bench import flops, trace


def read(ctx):
    H = ctx.cell.config["merinda"]["hidden"]
    events = trace.named(trace.op_events(ctx.trace.device), "gru_scan")
    seconds = sum(e.dur for e in events) * 1e-9
    f = b = 0.0
    for kind in ("refit_step", "recover"):
        for F, S, k in ctx.traced_calls(kind):
            f += flops.gru_flops(F * S, k, flops.N + flops.M, H)
            b += flops.gru_bytes(F * S, k, flops.N + flops.M, H)
    share = flops.roofline_pct(f, b, seconds, ctx.peaks)
    return None if share is None else share[0]

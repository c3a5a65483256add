"""Share of the roofline reached by the `rk4_poly_solve` Pallas kernel
inside ticks: guard and promote rollouts, and the refit step's decode."""
from bench import flops, trace


def read(ctx):
    events = trace.inside(
        trace.named(trace.op_events(ctx.trace.device), "rk4_poly_solve"),
        ctx.trace.annotations("tick"))
    seconds = sum(e.dur for e in events) * 1e-9
    f = b = 0.0
    for B, T in ctx.traced_calls("rk4_guard"):
        f += flops.rk4_flops(B, T)
        b += flops.rk4_bytes(B, T)
    for F, S, k in ctx.traced_calls("refit_step"):
        f += flops.rk4_flops(F * S, k)
        b += flops.rk4_bytes(F * S, k)
    share = flops.roofline_pct(f, b, seconds, ctx.peaks)
    return None if share is None else share[0]

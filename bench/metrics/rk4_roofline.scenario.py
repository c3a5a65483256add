"""Share of the roofline reached by the `rk4_poly_solve` Pallas kernel
inside what-if queries: one [ensemble x K] rollout per query."""
from bench import flops, trace


def read(ctx):
    events = trace.inside(
        trace.named(trace.op_events(ctx.trace.device), "rk4_poly_solve"),
        ctx.trace.annotations("scenario"))
    seconds = sum(e.dur for e in events) * 1e-9
    f = b = 0.0
    for B, T in ctx.traced_calls("rk4_scenario"):
        f += flops.rk4_flops(B, T)
        b += flops.rk4_bytes(B, T)
    share = flops.roofline_pct(f, b, seconds, ctx.peaks)
    return None if share is None else share[0]

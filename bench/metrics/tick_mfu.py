"""The ticks' model work over the traced ticks' wall time and the chip's
peak, %.  Work: guard and promote rollouts, each refit step's forward and
backward (3 x forward), and model recovery."""
from bench import flops


def read(ctx):
    m, d = ctx.cell.config["merinda"], ctx.cell.dims
    seconds = sum(ctx.traced.get("tick_s") or [])
    f = sum(flops.rk4_flops(B, T, **d)
            for B, T in ctx.traced_calls("rk4_guard"))
    f += sum(3 * flops.merinda_forward_flops(F * S, k, m["hidden"],
                                             m["head_hidden"], **d)
             for F, S, k in ctx.traced_calls("refit_step"))
    f += sum(flops.encode_flops(F * S, k, m["hidden"], m["head_hidden"],
                                **d)
             for F, S, k in ctx.traced_calls("recover"))
    if seconds <= 0 or f <= 0:
        return None
    return 100.0 * f / seconds / ctx.peaks["flops_per_s"]

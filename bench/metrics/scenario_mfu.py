"""The what-if rollouts' work over the traced queries' wall time and the
chip's peak, %."""
from bench import flops


def read(ctx):
    seconds = sum(ctx.traced.get("scenario_s") or [])
    f = sum(flops.rk4_flops(B, T, **ctx.cell.dims)
            for B, T in ctx.traced_calls("rk4_scenario"))
    if seconds <= 0 or f <= 0:
        return None
    return 100.0 * f / seconds / ctx.peaks["flops_per_s"]

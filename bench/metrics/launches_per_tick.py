"""Device program executions ("XLA Modules" events) that start inside the
harness's tick annotations, per traced tick."""
from bench import trace


def read(ctx):
    ticks = ctx.trace.annotations("tick")
    mods = trace.module_events(ctx.trace.device)
    if not ticks or not mods:
        return None
    return len(trace.inside(mods, ticks)) / len(ticks)

"""The program's `sync` spans (the serving thread waiting on the device)
inside its root tick spans, per traced tick."""
from bench import spans


def read(ctx):
    ticks = ctx.traced.get("ticks", 0)
    syncs = spans.tick_syncs(ctx.spans)
    if not ticks or not syncs:
        return None
    return len(syncs) / ticks

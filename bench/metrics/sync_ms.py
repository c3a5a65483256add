"""Time the serving thread waited on the device inside ticks: the summed
`sync` spans inside the program's root tick spans, ms per traced tick."""
from bench import spans


def read(ctx):
    return spans.ms_per(spans.tick_syncs(ctx.spans), ctx.traced.get("ticks", 0))

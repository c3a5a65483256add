"""The program's `guard` spans, summed over shards, mean per traced tick, ms."""


def read(ctx):
    return ctx.span_ms_per_tick("guard")

"""Time a what-if query waited on the device: the summed `sync` spans
inside the program's `scenario` spans, ms per traced query."""
from bench import spans


def read(ctx):
    return spans.ms_per(spans.syncs_inside(ctx.spans, ("scenario",)),
                        len(ctx.traced.get("scenario_s") or []))

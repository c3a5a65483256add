"""The program's `sync` spans: where its serving thread waited on the device.

`repro.obs.Tracer` wraps every host read of a device value, and the tick's
final block, in a span named `sync` whose `site` arg names the place.  The
readers below take them from the tracer's Chrome trace events (the
`Context.spans` of a traced run) and keep those nested in a given span on
the same thread.  A program without such spans gives an empty list.
"""
from __future__ import annotations

from bisect import bisect_right

# the tracer's clock is in microseconds; a child's end may round past its
# parent's by far less than this
_EPS_US = 1e-3


def syncs_inside(spans: list, parents: tuple[str, ...]) -> list[dict]:
    """`sync` events that lie inside an event named in `parents` on the
    same thread (events of one name on one thread do not overlap)."""
    xs = [e for e in spans if e.get("ph") == "X"]
    outer: dict[int, list[tuple[float, float]]] = {}
    for e in xs:
        if e["name"] in parents:
            outer.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
    for ivs in outer.values():
        ivs.sort()
    starts = {tid: [s for s, _ in ivs] for tid, ivs in outer.items()}
    out = []
    for e in xs:
        if e["name"] != "sync" or e["tid"] not in outer:
            continue
        i = bisect_right(starts[e["tid"]], e["ts"]) - 1
        if i >= 0 and e["ts"] + e["dur"] <= outer[e["tid"]][i][1] + _EPS_US:
            out.append(e)
    return out


def tick_syncs(spans: list) -> list[dict]:
    """`sync` events inside the root tick spans: `sharded_tick` where the
    server is sharded, else `tick`."""
    sharded = any(e.get("ph") == "X" and e["name"] == "sharded_tick"
                  for e in spans)
    return syncs_inside(spans, ("sharded_tick",) if sharded else ("tick",))


def ms_per(events: list[dict], n: int) -> float | None:
    """Summed duration of `events`, ms, over `n`; None where either is 0."""
    if not events or not n:
        return None
    return sum(e["dur"] for e in events) * 1e-3 / n

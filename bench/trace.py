"""From a profiler trace and the program's spans to the per-layer numbers.

The JAX profiler writes an `.xplane.pb`; `jax.profiler.ProfileData` reads
it.  Device planes (`/device:TPU:<i>`) carry the operations that ran on each
chip ("XLA Ops") and the programs launched ("XLA Modules"); the host plane
carries the harness's `TraceAnnotation`s (`ingest`, `tick`, `scenario`) on
the same clock.  `load` keeps only those, as plain tuples, so that the
reduction below can be checked on a small recorded trace.

The program's own spans (`repro.obs.Tracer`: tick, flush, guard, schedule,
refit, ...) are on the host's `perf_counter`, not on the profiler's clock.
They are moved onto it by the median offset between each harness `tick`
annotation and the program's root tick span of the same loop, and then name
what the host was doing in each gap in which the device was idle.
"""
from __future__ import annotations

import glob
import shutil
from dataclasses import dataclass, field

import numpy as np

ANNOTATIONS = ("ingest", "tick", "scenario")
STAGES = ("flush", "guard", "schedule", "refit", "rebalance", "scenario",
          "pump_flush", "restart_shard")


@dataclass
class DeviceEvent:
    chip: int
    line: str
    name: str          # on a TPU, the op's HLO text: "%gru_scan.1 = ..."
    start: float       # ns, profiler clock
    dur: float         # ns

    @property
    def op(self) -> str:
        """The op's own name: 'gru_scan' for '%gru_scan.1 = (f32[...]) ...'
        (a Pallas kernel's op is named after the kernel)."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        base, _, suffix = head.rpartition(".")
        return base if base and suffix.isdigit() else head


def load(trace_dir: str) -> dict:
    """Device events and harness annotations of the newest trace in a dir."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            try:
                chip = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            for line in plane.lines:
                for ev in line.events:
                    device.append(DeviceEvent(chip, line.name, ev.name,
                                              float(ev.start_ns),
                                              float(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ANNOTATIONS:
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns)))
    return {"device": device, "host": sorted(host, key=lambda a: a[1])}


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in clip(union(intervals), lo, hi))


def op_events(device: list[DeviceEvent]) -> list[DeviceEvent]:
    """The events of operations that ran (the "XLA Ops" lines)."""
    ops = [e for e in device if e.line == "XLA Ops"]
    return ops or [e for e in device if e.line not in ("XLA Modules",
                                                       "Steps")]


def module_events(device: list[DeviceEvent]) -> list[DeviceEvent]:
    return [e for e in device if e.line == "XLA Modules"]


def named(events, kernel: str) -> list[DeviceEvent]:
    """Events of one kernel's own op (not of the ops that consume it)."""
    return [e for e in events if e.op == kernel]


def inside(events, spans) -> list[DeviceEvent]:
    """Events whose start falls inside one of `spans` [(start, end)]."""
    spans = sorted(spans)
    starts = np.asarray([s for s, _ in spans])
    ends = np.asarray([e for _, e in spans])
    out = []
    for ev in events:
        i = int(np.searchsorted(starts, ev.start, side="right")) - 1
        if i >= 0 and ev.start <= ends[i]:
            out.append(ev)
    return out


@dataclass
class Reduced:
    device: list
    host: list                       # [(name, start, end)] annotations
    stages: list                     # [(name, start, end)] program spans
    chips: int
    lo: float = 0.0
    hi: float = 0.0
    gaps: dict = field(default_factory=dict)

    def annotations(self, name: str) -> list[tuple[float, float]]:
        return [(s, e) for n, s, e in self.host if n == name]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        ops = op_events(self.device)
        per_chip = [covered([(e.start, e.start + e.dur) for e in ops
                             if e.chip == c], self.lo, self.hi)
                    for c in sorted({e.chip for e in ops})]
        return sum(per_chip) / self.chips * 1e-9 if per_chip else 0.0

    def breakdown(self) -> dict:
        total: dict[str, float] = {}
        for e in op_events(self.device):
            if e.start < self.hi and e.start + e.dur > self.lo:
                total[e.op] = total.get(e.op, 0.0) + e.dur
        ops = sorted(total.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, v * 1e-9 / self.chips] for n, v in ops],
                "idle_gaps": [[n, v * 1e-9] for n, v in gaps]}


def label_gaps(busy, lo, hi, stages, host) -> dict[str, float]:
    """Idle nanoseconds by what the host was doing: the innermost program
    span open at the gap's middle, else the harness annotation, else
    'between_calls'."""
    out: dict[str, float] = {}
    t = lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            mid = 0.5 * (s + t)
            open_stage = [(e2 - s2, n) for n, s2, e2 in stages
                          if s2 <= mid <= e2]
            if open_stage:
                label = min(open_stage)[1]
            else:
                open_host = [(e2 - s2, n) for n, s2, e2 in host
                             if s2 <= mid <= e2]
                label = min(open_host)[1] if open_host else "between_calls"
            out[label] = out.get(label, 0.0) + (s - t)
        t = max(t, e)
    return out


def spans_on_profiler_clock(spans: list, host: list) -> list:
    """Program spans [(name, start_ns, end_ns)] on the profiler's clock."""
    roots = [ev for ev in spans if ev.get("ph") == "X"
             and ev["name"] in ("tick", "sharded_tick")]
    if any(ev["name"] == "sharded_tick" for ev in roots):
        roots = [ev for ev in roots if ev["name"] == "sharded_tick"]
    ticks = [s for n, s, _ in host if n == "tick"]
    pairs = min(len(ticks), len(roots))
    if not pairs:
        return []
    offset = float(np.median([ticks[i] - roots[i]["ts"] * 1e3
                              for i in range(pairs)]))
    return [(ev["name"], ev["ts"] * 1e3 + offset,
             (ev["ts"] + ev["dur"]) * 1e3 + offset)
            for ev in spans if ev.get("ph") == "X" and ev["name"] in STAGES]


def reduce_loaded(loaded: dict, spans: list, chips: int) -> Reduced:
    host = loaded["host"]
    if not host:
        raise RuntimeError("the trace holds none of the harness's "
                           "annotations")
    lo = min(s for _, s, _ in host)
    hi = max(e for _, _, e in host)
    red = Reduced(device=loaded["device"], host=host,
                  stages=spans_on_profiler_clock(spans, host), chips=chips,
                  lo=lo, hi=hi)
    ops = op_events(red.device)
    chip0 = min((e.chip for e in ops), default=0)
    busy = clip(union([(e.start, e.start + e.dur) for e in ops
                       if e.chip == chip0]), lo, hi)
    red.gaps = label_gaps(busy, lo, hi, red.stages, host)
    return red


def reduce(trace_dir: str, tracer, chips: int) -> Reduced:
    spans = tracer.to_chrome_trace()["traceEvents"] if tracer else []
    return reduce_loaded(load(trace_dir), spans, chips)


def cleanup(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


@dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: object
    trace: Reduced
    window: object            # harness.Window of the whole run
    traced: dict              # the traced part: ticks, tick_s, ingest_s, ...
    calls: list               # (phase, traced, kind, shape) of every call
    peaks: dict
    spans: list               # the program's spans (Chrome trace events)

    def traced_calls(self, kind: str) -> list[tuple]:
        return [shape for phase, traced, k, shape in self.calls
                if traced and k == kind]

    def span_ms_per_tick(self, name: str) -> float | None:
        ticks = self.traced.get("ticks", 0)
        durs = [ev["dur"] for ev in self.spans
                if ev.get("ph") == "X" and ev["name"] == name]
        if not ticks or not durs:
            return None
        return sum(durs) * 1e-3 / ticks

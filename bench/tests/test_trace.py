"""The reduction from a trace to the per-layer numbers, on a small trace
worked by hand, and the operation and byte counts on hand-worked shapes."""
import pytest

from bench import flops, trace
from bench.trace import DeviceEvent

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _hand_trace():
    # op names as a TPU trace gives them: the op's HLO text
    ops = [DeviceEvent(0, "XLA Ops", "%fusion.1 = f32[8]{0} fusion(...)",
                       100, 100),
           DeviceEvent(0, "XLA Ops", "%gru_scan.1 = (f32[8,24,32]) "
                       "custom-call(...)", 150, 150),
           DeviceEvent(0, "XLA Ops", "%rk4_poly_solve = f32[64,33,3] "
                       "custom-call(...)", 500, 100),
           DeviceEvent(0, "XLA Ops", "%rk4_poly_solve.12 = f32[32,51,3] "
                       "custom-call(...)", 900, 50),
           # a consumer of the kernel's result is not the kernel
           DeviceEvent(0, "XLA Ops", "%multiply_reduce_fusion = f32[64] "
                       "fusion(f32[64,33,3] %rk4_poly_solve)", 960, 10)]
    mods = [DeviceEvent(0, "XLA Modules", "jit_tick_a", 90, 5),
            DeviceEvent(0, "XLA Modules", "jit_tick_b", 480, 5),
            DeviceEvent(0, "XLA Modules", "jit_roll", 880, 5)]
    host = [("ingest", 0.0, 80.0), ("tick", 80.0, 700.0),
            ("scenario", 850.0, 1000.0)]
    # the program's spans, microseconds on its own clock, 30 ns behind
    spans = [{"ph": "X", "name": "tick", "ts": 0.05, "dur": 0.6},
             {"ph": "X", "name": "guard", "ts": 0.22, "dur": 0.2}]
    return trace.reduce_loaded({"device": ops + mods, "host": host}, spans,
                               chips=1)


def test_busy_union_and_idle_share():
    red = _hand_trace()
    assert red.window_s == pytest.approx(1000e-9)
    # [100, 300] + [500, 600] + [900, 950] + [960, 970]: overlaps once
    assert red.busy_s == pytest.approx(360e-9)


def test_kernel_time_by_the_op_name():
    ops = trace.op_events(_hand_trace().device)
    assert sum(e.dur for e in trace.named(ops, "gru_scan")) == 150
    assert sum(e.dur for e in trace.named(ops, "rk4_poly_solve")) == 150


def test_attribution_to_tick_and_scenario_annotations():
    red = _hand_trace()
    rk4 = trace.named(trace.op_events(red.device), "rk4_poly_solve")
    assert [e.start for e in trace.inside(rk4, red.annotations("tick"))] \
        == [500]
    assert [e.start for e in trace.inside(rk4, red.annotations("scenario"))] \
        == [900]


def test_launches_per_tick():
    from bench.harness import load_module, BENCH
    red = _hand_trace()
    ctx = trace.Context(cell=None, trace=red, window=None, traced={},
                        calls=[], peaks=PEAKS, spans=[])
    reader = load_module(BENCH / "metrics" / "launches_per_tick.py")
    assert reader.read(ctx) == 2.0      # two programs start inside the tick


def test_idle_gaps_named_by_span_then_annotation():
    red = _hand_trace()
    # the guard span lands on [250, 450] on the profiler's clock
    (name, start, end), = red.stages
    assert name == "guard"
    assert (start, end) == (pytest.approx(250.0), pytest.approx(450.0))
    assert red.gaps == {"ingest": 100.0, "guard": 200.0,
                        "between_calls": 300.0, "scenario": 40.0}
    assert red.breakdown()["idle_gaps"][0] == ["between_calls",
                                               pytest.approx(300e-9)]


def test_hand_worked_counts():
    # per step: 2*4*6 + 2*2*4 + 2*2*2 + 10*2 = 92; 2 rows x 3 steps
    assert flops.gru_flops(2, 3, 4, 2) == 552
    assert flops.gru_bytes(2, 3, 4, 2) == 4 * (24 + 4 + 42 + 12 + 4)
    # L = 35; per stage 2*35 + 2*3*35 + 6 = 286; 4 stages + 18
    assert flops.rk4_flops(1, 1) == 1162
    assert flops.rk4_bytes(1, 1) == 4 * (105 + 3 + 1 + 6)


def test_roofline_share_and_bound():
    share, bound = flops.roofline_pct(197e9, 819e6 / 2, 2e-3, PEAKS)
    assert share == pytest.approx(50.0) and bound == "compute"
    share, bound = flops.roofline_pct(197e9 / 4, 819e6, 4e-3, PEAKS)
    assert share == pytest.approx(25.0) and bound == "memory"
    assert flops.roofline_pct(1.0, 1.0, 0.0, PEAKS) is None


def test_no_tpu_and_unknown_device_are_refused():
    import jax
    from bench.harness import NoChip, device_info, peak_of
    assert peak_of("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(NoChip):
        peak_of("cpu")
    with pytest.raises(NoChip):
        device_info(jax, 1, require_chip=True)     # the tests run on the CPU

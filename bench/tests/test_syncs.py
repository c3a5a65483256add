"""The `sync`-span readers on hand-built span lists, and the trace
reduction's annotations with the program's spans mirrored beside them."""
import pytest

from bench import spans, trace
from bench.harness import BENCH, load_module

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _x(name, ts, dur, tid=0, **args):
    ev = {"ph": "X", "name": name, "cat": "twin", "ts": ts, "dur": dur,
          "pid": 0, "tid": tid}
    if args:
        ev["args"] = args
    return ev


def _hand_spans():
    # microseconds, as the tracer writes them: two ticks and one query
    return [{"ph": "M", "name": "thread_name", "pid": 0, "tid": 0,
             "args": {"name": "MainThread"}},
            _x("tick", 0.0, 100.0, tick=1),
            _x("guard", 5.0, 30.0),
            _x("sync", 10.0, 5.0, site="guard.scores"),
            _x("refit", 40.0, 60.0),
            _x("sync", 50.0, 20.0, site="refit.loss"),
            _x("sync", 80.0, 20.0, site="tick.block"),   # ends with the tick
            _x("scenario", 140.0, 30.0, twin=3),
            _x("rollout", 145.0, 20.0),
            _x("sync", 150.0, 4.0, site="scenario.result"),
            _x("sync", 155.0, 6.0, site="scenario.result"),
            _x("tick", 200.0, 100.0, tick=2),
            _x("sync", 250.0, 10.0, site="guard.scores"),
            # another thread's span inside a tick's interval is not the tick's
            _x("pump_flush", 210.0, 30.0, tid=1),
            _x("sync", 215.0, 5.0, tid=1, site="elsewhere")]


def _read(name, span_list, ticks=2, queries=1):
    ctx = trace.Context(cell=None, trace=None, window=None,
                        traced={"ticks": ticks,
                                "scenario_s": [0.01] * queries},
                        calls=[], peaks=PEAKS, spans=span_list)
    return load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


def test_readers_hand_worked():
    s = _hand_spans()
    # 4 syncs inside the two ticks: 5 + 20 + 20 + 10 us
    assert _read("host_syncs_per_tick", s) == 2.0
    assert _read("sync_ms", s) == pytest.approx(55e-3 / 2)
    # 2 syncs inside the one query: 4 + 6 us
    assert _read("scenario_sync_ms", s) == pytest.approx(10e-3)


def test_readers_none_without_sync_spans():
    s = [e for e in _hand_spans() if e["name"] != "sync"]
    for name in ("host_syncs_per_tick", "sync_ms", "scenario_sync_ms"):
        assert _read(name, s) is None
        assert _read(name, []) is None


def test_sharded_ticks_count_the_whole_root():
    # shard ticks under one sharded_tick, and a rebalance read between them
    s = [_x("sharded_tick", 0.0, 300.0),
         _x("tick", 10.0, 100.0, shard="0"),
         _x("sync", 20.0, 10.0, site="guard.scores"),
         _x("tick", 120.0, 100.0, shard="1"),
         _x("sync", 130.0, 10.0, site="guard.scores"),
         _x("rebalance", 230.0, 50.0),
         _x("sync", 240.0, 20.0, site="rebalance.pressure")]
    assert [e["args"]["site"] for e in spans.tick_syncs(s)] == [
        "guard.scores", "guard.scores", "rebalance.pressure"]
    assert _read("host_syncs_per_tick", s, ticks=1) == 3.0
    assert _read("sync_ms", s, ticks=1) == pytest.approx(40e-3)


def test_load_keeps_only_harness_annotations(tmp_path):
    """With the program's spans mirrored into the profiler trace, the
    reduction's host annotations are exactly the harness's, as before."""
    import jax
    from repro.obs import Tracer

    tracer = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("ingest"):
            pass
        with jax.profiler.TraceAnnotation("tick"):
            with tracer.span("tick", tick=1):
                with tracer.span("sync", site="tick.block"):
                    pass
        with jax.profiler.TraceAnnotation("scenario"):
            with tracer.span("scenario", twin=0):
                pass
    finally:
        jax.profiler.stop_trace()
    loaded = trace.load(str(tmp_path))
    assert [n for n, _, _ in loaded["host"]] == ["ingest", "tick", "scenario"]

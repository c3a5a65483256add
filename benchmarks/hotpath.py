"""Serving hot-path microbenchmark: GRU scan + RK4 roll, per backend.

Times the two fused kernels of the online serving loop — the GRU sequence
scan (refit encoder) and the RK4 polynomial rollout (refit decoder + guard) —
at SERVING batch shapes, across the three backends the wrappers dispatch to:

  * ``reference``        — the pure-jnp oracle under jit (the CPU baseline
    every serving number so far was measured on),
  * ``pallas_interpret`` — the Pallas kernel in interpreter mode (what CI and
    CPU runs of ``use_pallas=True`` execute; semantics of the compiled
    kernel, interpreter cost),
  * ``pallas_compiled``  — the compiled Pallas kernel.  It runs only on a
    TPU and its rows read ``n/a`` elsewhere.  On a TPU it is timed like the
    others: a failure to compile or run fails the benchmark.

Each kernel is timed on its two serving invocations: ``fwd`` (guard / predict
rollouts) and ``grad`` (the refit train step's value_and_grad, which for the
Pallas backend runs the kernel forward + the reference backward via the
custom-VJP rule — so `grad` rows price the full training hot path, not just
the kernel).  Shapes mirror the 64-twin online benchmark (refit: 8 slots x 8
windows, window 24; guard: budget-128 fused call, window 32) plus a 10k-scale
guard shape.  Every row names the platform, device kind and device count
it ran on: a time from a CPU run is a CPU time, never a device number.
Emitted to bench_out/hotpath.csv by ``benchmarks/run.py --only hotpath``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import print_rows, time_fn, write_csv
from repro.core.library import make_library
from repro.kernels.gru.ops import gru_scan
from repro.kernels.gru.ref import init_gru_params
from repro.kernels.rk4.ops import rk4_poly_solve

def _backends() -> list[tuple[str, dict | None]]:
    """(name, wrapper kwargs); None where the backend cannot exist on this
    platform — the compiled kernel off the TPU — and nothing else."""
    on_tpu = jax.default_backend() == "tpu"
    return [
        ("reference", dict(use_pallas=False)),
        ("pallas_interpret", dict(use_pallas=True, interpret=True)),
        ("pallas_compiled",
         dict(use_pallas=True, interpret=False) if on_tpu else None),
    ]


def _device_cols() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "devices": len(jax.devices())}


def _time(fn, grad_fn, kw) -> tuple[float | None, float | None]:
    """(fwd_ms, grad_ms); (None, None) for a backend this platform lacks."""
    if kw is None:
        return None, None
    return 1e3 * time_fn(fn), 1e3 * time_fn(grad_fn)


def _gru_rows(B, T, D, H, tag):
    key = jax.random.PRNGKey(0)
    p = init_gru_params(key, D, H)
    xs = jax.random.normal(key, (B, T, D))
    h0 = jnp.zeros((B, H))
    rows = []
    for name, kw in _backends():
        def loss(wx):
            hs, hT = gru_scan(xs, h0, wx, p["wh"], p["b"], **kw)
            return jnp.sum(hT ** 2) + jnp.mean(hs ** 2)

        # jit once per backend: timing must price the compiled step, not
        # per-call retracing of jax.grad
        grad_fn = jax.jit(jax.grad(loss))

        def fwd():
            return gru_scan(xs, h0, p["wx"], p["wh"], p["b"], **kw)

        def grad():
            return grad_fn(p["wx"])

        fwd_ms, grad_ms = _time(fwd, grad, kw)
        rows.append({"op": "gru_scan", "shape": tag,
                     "B": B, "T": T, "backend": name, **_device_cols(),
                     "fwd_ms": _fmt(fwd_ms), "grad_ms": _fmt(grad_ms)})
    return rows


def _rk4_rows(B, T, n, m, order, tag):
    lib = make_library(n, m, order)
    key = jax.random.PRNGKey(1)
    theta = 0.1 * jax.random.normal(key, (B, n, lib.size))
    y0 = 0.3 * jax.random.normal(key, (B, n))
    us = 0.2 * jax.random.normal(key, (B, T, m))
    rows = []
    for name, kw in _backends():
        def loss(th):
            ys = rk4_poly_solve(th, y0, us, dt=0.02, library=lib, **kw)
            return jnp.mean(ys ** 2)

        grad_fn = jax.jit(jax.grad(loss))

        def fwd():
            return rk4_poly_solve(theta, y0, us, dt=0.02, library=lib, **kw)

        def grad():
            return grad_fn(theta)

        fwd_ms, grad_ms = _time(fwd, grad, kw)
        rows.append({"op": "rk4_roll", "shape": tag,
                     "B": B, "T": T, "backend": name, **_device_cols(),
                     "fwd_ms": _fmt(fwd_ms), "grad_ms": _fmt(grad_ms)})
    return rows


def _fmt(ms: float | None):
    return "n/a" if ms is None else round(ms, 3)


def run(quick: bool = True, smoke: bool = False) -> None:
    # serving shapes: refit encoder sees refit_slots*windows_per_twin window
    # batches; the guard's fused call is budget (+carry) wide.
    if smoke:
        shapes_gru = [(16, 16, 5, 16, "smoke")]
        shapes_rk4 = [(16, 16, 4, 1, 2, "smoke")]
    else:
        shapes_gru = [(64, 24, 5, 32, "refit-64twin"),
                      (128, 24, 5, 32, "refit-128slotwin")]
        shapes_rk4 = [(64, 24, 4, 1, 3, "refit-64twin"),
                      (128, 32, 4, 1, 3, "guard-budget128"),
                      (512, 32, 4, 1, 3, "guard-budget512")]
        if not quick:
            shapes_gru.append((512, 24, 5, 32, "refit-512slotwin"))
            shapes_rk4.append((2048, 32, 4, 1, 3, "guard-10kscale"))
    rows = []
    for B, T, D, H, tag in shapes_gru:
        rows += _gru_rows(B, T, D, H, tag)
    for B, T, n, m, order, tag in shapes_rk4:
        rows += _rk4_rows(B, T, n, m, order, tag)
    dev = _device_cols()
    print_rows("serving hot path: reference vs pallas backends "
               f"(platform={dev['platform']}, device_kind="
               f"{dev['device_kind']}, devices={dev['devices']})", rows)
    path = write_csv("hotpath.csv", rows)
    print(f"[hotpath] wrote {path}")


if __name__ == "__main__":
    run()

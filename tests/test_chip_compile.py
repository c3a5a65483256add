"""Compile the serving Pallas kernels for a described TPU v5e chip.

Interpret-mode parity (tests/test_kernels_*.py, tests/test_hotpath_parity.py)
checks the kernels' semantics, not whether Mosaic can lower them: a kernel
can pass every interpret-mode test and still be refused by the TPU
compiler.  These tests ask the installed TPU compiler to compile each
serving kernel for a chip that is described, not attached
(`jax.experimental.topologies`), with ``interpret=False``, and check that
the compiled program really contains the Pallas kernel
(``tpu_custom_call``).  Nothing runs: numeric parity on the chip is
`chip_smoke.py` phase (a).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and every test worker imports every test file.  Where the topology cannot
be described, the fixture skips these tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fleet import FleetConfig, FleetMerinda
from repro.core.library import make_library
from repro.core.merinda import MerindaConfig
from repro.kernels.gru.ops import gru_scan
from repro.kernels.rk4.ops import rk4_poly_solve
from repro.twin.scenario import ScenarioConfig, ScenarioRunner

F8_LIB = make_library(3, 1, 3)          # F-8: n=3, m=1, order 3 -> L=35
DT = 0.01


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: a
    compile for a described chip is written to the cache but cannot be read
    back without the chip, and the next read would warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(tree, sharding):
    """Shapes of `tree` (arrays or ShapeDtypeStructs) placed on `sharding`."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _f32(shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _compile_has_kernel(fn, args, sharding) -> None:
    compiled = jax.jit(fn).lower(*_spec(args, sharding)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _gru_args(B, T, D, H, lead=()):
    return (_f32(lead + (B, T, D)), _f32(lead + (B, H)),
            _f32(lead + (D, 3 * H)), _f32(lead + (H, 3 * H)),
            _f32(lead + (3 * H,)))


def _rk4_args(B, T, lead=()):
    lib = F8_LIB
    return (_f32(lead + (B, lib.n, lib.size)), _f32(lead + (B, lib.n)),
            _f32(lead + (B, T, lib.m)))


def _gru_fwd(xs, h0, wx, wh, b):
    return gru_scan(xs, h0, wx, wh, b, use_pallas=True, interpret=False)


def _rk4_fwd(theta, y0, us):
    return rk4_poly_solve(theta, y0, us, dt=DT, library=F8_LIB,
                          use_pallas=True, interpret=False)


# (B, T, Din, H): Din = n + m = 4 for the F-8 GRU encoder input
@pytest.mark.parametrize("B,T,D,H", [(64, 24, 4, 32), (128, 24, 4, 16)],
                         ids=["online", "online_scale"])
def test_gru_forward_compiles(one_chip, B, T, D, H):
    _compile_has_kernel(_gru_fwd, _gru_args(B, T, D, H), one_chip)


@pytest.mark.parametrize("B,T", [(128, 32), (64, 24)],
                         ids=["guard_budget128", "refit_64twin"])
def test_rk4_forward_compiles(one_chip, B, T):
    _compile_has_kernel(_rk4_fwd, _rk4_args(B, T), one_chip)


def test_gru_fleet_grad_compiles(one_chip):
    """vmap(grad) over 8 refit slots x 8 windows, per-slot weights: the
    pallas_call batching rule turns the fleet axis into a grid axis."""
    def loss(xs, h0, wx, wh, b):
        hs, hT = _gru_fwd(xs, h0, wx, wh, b)
        return jnp.sum(hT ** 2) + jnp.mean(hs ** 2)

    grad = jax.vmap(jax.grad(loss, argnums=(2, 3, 4)))
    _compile_has_kernel(grad, _gru_args(8, 24, 4, 32, lead=(8,)), one_chip)


def test_rk4_fleet_grad_compiles(one_chip):
    def loss(theta, y0, us):
        return jnp.mean(_rk4_fwd(theta, y0, us) ** 2)

    grad = jax.vmap(jax.grad(loss))
    _compile_has_kernel(grad, _rk4_args(8, 24, lead=(8,)), one_chip)


def test_scenario_grid_compiles(one_chip):
    """The scenario engine's fused [ensemble=4, K=8] rollout, its four
    results packed into the one array the server reads back."""
    runner = ScenarioRunner(F8_LIB, DT, ScenarioConfig(ensemble=4),
                            use_pallas=True, interpret=False)
    lib = F8_LIB
    args = (_f32((4, lib.n, lib.size)),
            jax.ShapeDtypeStruct((), jnp.int32),
            _f32((lib.n,)), _f32((8, 50, lib.m)))
    _compile_has_kernel(runner._roll_packed, args, one_chip)


def test_fleet_train_step_compiles(one_chip):
    """The refit step the server runs (online config: 8 slots x 8 windows,
    window 24, hidden 32), with both kernels inside vmap(value_and_grad)."""
    mcfg = MerindaConfig(n=3, m=1, order=3, dt=DT, hidden=32,
                         head_hidden=32, n_active=24, use_pallas=True,
                         interpret=False)
    fleet = FleetMerinda(FleetConfig(merinda=mcfg, fleet=8,
                                     windows_per_twin=8))
    state = jax.eval_shape(fleet.init, jax.random.PRNGKey(0))
    args = (state, _f32((8, 8, 25, 3)), _f32((8, 8, 24, 1)))
    _compile_has_kernel(lambda *a: fleet.train_step_per_slot(*a), args,
                        one_chip)

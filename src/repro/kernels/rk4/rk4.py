"""Fused RK4 polynomial-ODE integrator — Pallas TPU kernel.

This is the `SOLVE(Y(0), Theta, U)` block of MERINDA: the part of the MR
pipeline prior FPGA ODE-solver work could NOT accelerate because the model
coefficients are input-dependent (they arrive per-instance from the dense
head).  On TPU we make it MXU-shaped:

  * Library evaluation uses GATHER-AS-MATMUL: Phi = prod_o (Xaug @ S_o) with
    precomputed one-hot selection matrices S_o [1+n+m, L].  TPU has no cheap
    lane gather; a small matmul against a one-hot matrix runs on the MXU and
    pipelines perfectly (the CORDIC-analogue trick of DESIGN.md §2).
  * Theta stays pinned in VMEM across all T steps / 4 stages (ARRAY_PARTITION
    analogue) — per-instance coefficients are loaded exactly once.
  * The batch grid double-buffers tiles (PIPELINE II=1 analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.backend import MATMUL_PRECISION

__all__ = ["rk4_poly_solve_pallas", "selection_matrices"]


def selection_matrices(term_indices: np.ndarray, n_aug: int) -> np.ndarray:
    """term_indices [L, O] -> one-hot S [O, n_aug, L] with S[o, i, l] = 1 iff
    term l's o-th factor is Xaug[i]."""
    L, O = term_indices.shape
    sel = np.zeros((O, n_aug, L), dtype=np.float32)
    for o in range(O):
        sel[o, term_indices[:, o], np.arange(L)] = 1.0
    return sel


def _rk4_kernel(theta_ref, y0_ref, us_ref, sel_ref, ys_ref,
                *, dt: float, seq_len: int, order: int):
    theta = theta_ref[...].astype(jnp.float32)        # [Bt, n, L]
    sel = sel_ref[...].astype(jnp.float32)            # [O, n_aug, L]
    bt, n, L = theta.shape

    def rhs(y, u):
        ones = jnp.ones((bt, 1), jnp.float32)
        xaug = jnp.concatenate([ones, y, u], axis=-1)    # [Bt, 1+n+m]
        phi = jnp.ones((bt, L), jnp.float32)
        for o in range(order):                           # static unroll
            phi = phi * jnp.dot(xaug, sel[o], precision=MATMUL_PRECISION,
                                preferred_element_type=jnp.float32)
        return jnp.sum(phi[:, None, :] * theta, axis=-1)  # [Bt, n]

    def step(t, y):
        u = us_ref[:, t, :].astype(jnp.float32)
        k1 = rhs(y, u)
        k2 = rhs(y + 0.5 * dt * k1, u)
        k3 = rhs(y + 0.5 * dt * k2, u)
        k4 = rhs(y + dt * k3, u)
        y = y + (dt / 6.0) * (k1 + 2.0 * k2 + 2.0 * k3 + k4)
        ys_ref[:, t + 1, :] = y.astype(ys_ref.dtype)
        return y

    y0 = y0_ref[...].astype(jnp.float32)
    ys_ref[:, 0, :] = y0.astype(ys_ref.dtype)
    jax.lax.fori_loop(0, seq_len, step, y0)


def rk4_poly_solve_pallas(theta, y0, us, dt, sel, *, block_b: int = 8,
                          interpret: bool = False):
    """theta: [B, n, L], y0: [B, n], us: [B, T, m], sel: [O, n_aug, L]
    -> ys [B, T+1, n].  B must be a multiple of block_b (ops.py pads)."""
    B, n, L = theta.shape
    T = us.shape[1]
    m = us.shape[2]
    O, n_aug, _ = sel.shape
    assert n_aug == 1 + n + m, (n_aug, n, m)
    assert B % block_b == 0

    kernel = functools.partial(_rk4_kernel, dt=float(dt), seq_len=T, order=O)
    ys = pl.pallas_call(
        kernel,
        grid=(B // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, n, L), lambda i: (i, 0, 0)),    # theta tile
            pl.BlockSpec((block_b, n), lambda i: (i, 0)),          # y0 tile
            pl.BlockSpec((block_b, T, m), lambda i: (i, 0, 0)),    # us tile
            pl.BlockSpec((O, n_aug, L), lambda i: (0, 0, 0)),      # sel (pinned)
        ],
        out_specs=pl.BlockSpec((block_b, T + 1, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T + 1, n), theta.dtype),
        interpret=interpret,
        name="rk4_poly_solve",
    )(theta, y0, us, sel)
    return ys

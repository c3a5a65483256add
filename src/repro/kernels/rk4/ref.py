"""Pure-jnp oracle for the fused RK4 polynomial-ODE integrator.

Contract (shared with the Pallas kernel):
  rk4_poly_solve(theta [B, n, L], y0 [B, n], us [B, T, m], dt,
                 term_indices [L, O]) -> ys [B, T+1, n]

integrating  dY/dt = theta @ Phi(Y, u)  with zero-order-hold inputs, where
Phi_l = prod_o Xaug[term_indices[l, o]] and Xaug = [1, Y, U].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.backend import MATMUL_PRECISION

__all__ = ["rk4_poly_solve_ref", "poly_features_ref"]


def poly_features_ref(y, u, term_indices):
    """y: [..., n], u: [..., m], term_indices: [L, O] -> Phi [..., L]."""
    aug = jnp.concatenate([jnp.ones_like(y[..., :1]), y, u], axis=-1)
    return jnp.prod(aug[..., jnp.asarray(term_indices)], axis=-1)


def rk4_poly_solve_ref(theta, y0, us, dt, term_indices):
    def rhs(y, u):
        phi = poly_features_ref(y, u, term_indices)          # [B, L]
        return jnp.einsum("bnl,bl->bn", theta, phi,
                          precision=MATMUL_PRECISION)

    def step(y, u):
        k1 = rhs(y, u)
        k2 = rhs(y + 0.5 * dt * k1, u)
        k3 = rhs(y + 0.5 * dt * k2, u)
        k4 = rhs(y + dt * k3, u)
        y = y + (dt / 6.0) * (k1 + 2 * k2 + 2 * k3 + k4)
        return y, y

    _, ys = jax.lax.scan(step, y0, jnp.swapaxes(us, 0, 1))
    return jnp.concatenate([y0[:, None], jnp.swapaxes(ys, 0, 1)], axis=1)

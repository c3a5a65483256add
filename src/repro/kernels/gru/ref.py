"""Pure-jnp oracle for the fused GRU sequence scan.

Single source of truth for the GRU math used everywhere (MERINDA encoder,
kernel tests, LM smoke paths).  Gate layout in the fused weight matrices is
[z | r | c] along the last axis.

    z_t = sigmoid(x_t Wx[:, :H]   + h Wh[:, :H]   + b[:H])
    r_t = sigmoid(x_t Wx[:, H:2H] + h Wh[:, H:2H] + b[H:2H])
    c_t = tanh   (x_t Wx[:, 2H:]  + (r_t * h) Wh[:, 2H:] + b[2H:])
    h_t = (1 - z_t) * h + z_t * c_t

matching the paper's Operations 1-3 (gates, reset-apply, candidate).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.backend import MATMUL_PRECISION

__all__ = ["gru_cell_ref", "gru_scan_ref", "init_gru_params"]


def _mm(a, b):
    return jnp.matmul(a, b, precision=MATMUL_PRECISION)


def init_gru_params(key, d_in: int, hidden: int, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    sx = 1.0 / jnp.sqrt(d_in)
    sh = 1.0 / jnp.sqrt(hidden)
    return {
        "wx": (jax.random.uniform(k1, (d_in, 3 * hidden), minval=-sx, maxval=sx)
               .astype(dtype)),
        "wh": (jax.random.uniform(k2, (hidden, 3 * hidden), minval=-sh, maxval=sh)
               .astype(dtype)),
        "b": jnp.zeros((3 * hidden,), dtype),
    }


def gru_cell_ref(h, x, wx, wh, b):
    """One GRU step. h: [..., H], x: [..., Din] -> new h."""
    H = h.shape[-1]
    xp = _mm(x, wx) + b                               # [..., 3H]
    hp2 = _mm(h, wh[:, :2 * H])                       # z/r hidden contribution
    z = jax.nn.sigmoid(xp[..., :H] + hp2[..., :H])
    r = jax.nn.sigmoid(xp[..., H:2 * H] + hp2[..., H:])
    c = jnp.tanh(xp[..., 2 * H:] + _mm(r * h, wh[:, 2 * H:]))
    return (1.0 - z) * h + z * c


def gru_scan_ref(xs, h0, wx, wh, b):
    """Scan the GRU over time.

    xs: [B, T, Din], h0: [B, H] -> (hs [B, T, H], hT [B, H]).
    """
    # Hoisted input projection: one large MXU matmul for every timestep
    # (the TPU analogue of ARRAY_PARTITION; see DESIGN.md §2).
    H = h0.shape[-1]
    xp = _mm(xs, wx) + b                               # [B, T, 3H]

    def step(h, xp_t):
        hp2 = _mm(h, wh[:, :2 * H])
        z = jax.nn.sigmoid(xp_t[..., :H] + hp2[..., :H])
        r = jax.nn.sigmoid(xp_t[..., H:2 * H] + hp2[..., H:])
        c = jnp.tanh(xp_t[..., 2 * H:] + _mm(r * h, wh[:, 2 * H:]))
        h = (1.0 - z) * h + z * c
        return h, h

    hT, hs = jax.lax.scan(step, h0, jnp.swapaxes(xp, 0, 1))
    return jnp.swapaxes(hs, 0, 1), hT

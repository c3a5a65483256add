"""Fused GRU sequence-scan Pallas TPU kernel — the paper's accelerated core.

FPGA -> TPU mapping (DESIGN.md §2):
  * ARRAY_PARTITION complete  -> Wx/Wh/b pinned in VMEM for the whole scan
    (BlockSpec index_map broadcasts the full weight block to every grid step),
    and the per-timestep input projections hoisted into ONE MXU matmul.
  * PIPELINE II=1             -> the pallas grid pipelines batch tiles:
    while tile i computes, tile i+1's activations are DMA'd HBM->VMEM.
  * Operations 1-3 fusion     -> z/r share a single [H, 2H] matmul; the
    candidate is a second [H, H] matmul; all gate elementwise math stays in
    registers (VPU) — no HBM round-trips between timesteps.

The wrapper (ops.py) pads only the batch axis, to a pow2 multiple of the
batch tile; T, Din and H reach the kernel at their true sizes.

The hoisted projections are staged in a VMEM scratch ref and each timestep
reads its row with a dynamic ref index.  A dynamic slice of a VALUE by the
loop index does not lower on the TPU (Mosaic has no ``dynamic_slice``),
while dynamic indexing of a ref does, and the loop stays rolled: code size
does not grow with T.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import MATMUL_PRECISION

__all__ = ["gru_scan_pallas"]


def _gru_kernel(xs_ref, h0_ref, wx_ref, wh_ref, b_ref, hs_ref, hT_ref,
                xp_ref, *, hidden: int, seq_len: int):
    """One batch tile: hoisted input matmul + fused recurrent scan."""
    H = hidden
    xs = xs_ref[...]                                  # [Bt, T, Din]
    bt, T, d_in = xs.shape
    wx = wx_ref[...]                                  # [Din, 3H]
    wh = wh_ref[...]                                  # [H, 3H]
    b = b_ref[...]                                    # [1, 3H]

    # --- Stage 1: hoist all T input projections into one MXU matmul. ------
    xp = jnp.dot(xs.reshape(bt * T, d_in), wx, precision=MATMUL_PRECISION,
                 preferred_element_type=jnp.float32)
    xp_ref[...] = (xp + b).reshape(bt, T, 3 * H)

    wh_zr = wh[:, :2 * H]
    wh_c = wh[:, 2 * H:]

    # --- Stage 2: recurrent scan, weights resident in VMEM. ---------------
    def step(t, h):
        xp_t = xp_ref[:, t, :]                        # [Bt, 3H]
        hp = jnp.dot(h, wh_zr, precision=MATMUL_PRECISION,
                     preferred_element_type=jnp.float32)
        z = jax.nn.sigmoid(xp_t[:, :H] + hp[:, :H])
        r = jax.nn.sigmoid(xp_t[:, H:2 * H] + hp[:, H:])
        c = jnp.tanh(xp_t[:, 2 * H:]
                     + jnp.dot(r * h, wh_c, precision=MATMUL_PRECISION,
                               preferred_element_type=jnp.float32))
        h = (1.0 - z) * h + z * c
        hs_ref[:, t, :] = h.astype(hs_ref.dtype)
        return h

    h = h0_ref[...].astype(jnp.float32)
    h = jax.lax.fori_loop(0, seq_len, step, h)
    hT_ref[...] = h.astype(hT_ref.dtype)


def gru_scan_pallas(xs, h0, wx, wh, b, *, block_b: int = 8,
                    interpret: bool = False):
    """xs: [B, T, Din], h0: [B, H] -> (hs [B, T, H], hT [B, H]).

    B must be a multiple of block_b (ops.py pads).  Weights are mapped fully
    into VMEM (index_map -> block 0) for every batch-tile grid step.
    """
    B, T, d_in = xs.shape
    H = h0.shape[-1]
    assert B % block_b == 0, (B, block_b)
    b2 = b.reshape(1, -1)

    grid = (B // block_b,)
    kernel = functools.partial(_gru_kernel, hidden=H, seq_len=T)
    hs, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, T, d_in), lambda i: (i, 0, 0)),   # xs tile
            pl.BlockSpec((block_b, H), lambda i: (i, 0)),            # h0 tile
            pl.BlockSpec((d_in, 3 * H), lambda i: (0, 0)),           # Wx (pinned)
            pl.BlockSpec((H, 3 * H), lambda i: (0, 0)),              # Wh (pinned)
            pl.BlockSpec((1, 3 * H), lambda i: (0, 0)),              # b  (pinned)
        ],
        out_specs=[
            pl.BlockSpec((block_b, T, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H), xs.dtype),
            jax.ShapeDtypeStruct((B, H), h0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_b, T, 3 * H), jnp.float32)],
        interpret=interpret,
        name="gru_scan",
    )(xs, h0, wx, wh, b2)
    return hs, hT

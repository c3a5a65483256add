"""Backend selection for the Pallas kernel wrappers — decided in ONE place.

Every kernel wrapper in this package (kernels/gru, kernels/rk4,
kernels/linear_scan) takes the same pair of knobs:

  * ``use_pallas`` — False runs the pure-jnp reference (always available,
    fully differentiable); True dispatches the Pallas kernel.
  * ``interpret``  — how the Pallas kernel executes.  ``None`` (the default
    everywhere) means AUTO: compiled on a TPU backend, interpreter mode on
    everything else (CPU CI, dry-runs).  Passing an explicit bool overrides
    auto — e.g. ``interpret=True`` on TPU to debug a kernel.

Historically each call site carried its own ``interpret: bool = True``
default, which silently pinned interpreter mode even on real hardware and
let the defaults drift apart between the training and guard paths (the
server's guard said ``interpret=True`` while its config said otherwise).
`resolve_interpret` is now the single source of truth; call sites pass
``None`` through and the decision happens here, once per process.

`MATMUL_PRECISION` is the one matmul precision of the kernels and their
references.  On a TPU, an f32 matmul at default precision is a single bf16
pass: it keeps 8 bits of mantissa.  The RK4 kernel evaluates its library as
a matmul against one-hot selection matrices, a gather that must be exact,
and the references must round the way the kernels do, so both sides ask
for full f32.  The matmuls are tiny ([tile, 1+n+m] x [1+n+m, L] and
[tile, H] x [H, 3H]); the extra MXU passes cost little next to the scan.

`bucket_pow2` is the companion shape policy: Pallas batch padding rounds the
tile count up to a power of two, so the number of DISTINCT kernel shapes a
varying batch axis can produce is log2-bounded — the same trade the
ingestion path makes for its flush shapes (see data/pipeline.prepare_flush).
The cost is bounded 2x scratch work on padded rows; the payoff is a compile
cache that cannot grow linearly with fleet size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["MATMUL_PRECISION", "resolve_interpret", "bucket_pow2",
           "pad_batch"]

MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the ``interpret`` knob: None = auto (compiled only on TPU)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def bucket_pow2(size: int, quantum: int) -> int:
    """Round ``size`` up to ``quantum * 2**k`` (the padded batch size).

    Static-shape helper (called at trace time on python ints): kernels see
    at most log2(max_batch / quantum) distinct batch widths.
    """
    if size <= 0:
        return quantum
    tiles = -(-size // quantum)
    return quantum * (1 << (tiles - 1).bit_length())


def pad_batch(x, target: int):
    """Zero-pad axis 0 of ``x`` to ``target`` rows (no-op when already there).

    The Pallas wrappers pad with zeros and slice the scratch rows off after
    the kernel; zero rows are safe for both kernels (GRU zero inputs, RK4
    zero coefficients) and never feed gradients (padding happens inside the
    custom-VJP forward, backward replays the unpadded reference).
    """
    if x.shape[0] == target:
        return x
    widths = [(0, 0)] * x.ndim
    widths[0] = (0, target - x.shape[0])
    return jnp.pad(x, widths)

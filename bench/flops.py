"""Operations and bytes that each kernel's algorithm needs, from shapes.

Counts follow the algorithm, not the kernel's implementation: a one-hot
selection matmul that gathers library inputs costs nothing here, padding
rows cost nothing, and recomputation does not count.  A multiply-add is two
operations.  Bytes are the float32 operands read and results written once.

Shapes are those of the calls the window made (the unpadded batch):
  GRU scan  xs [B, T, d] with hidden H;
  RK4 solve theta [B, n, L], y0 [B, n], us [B, T, m] -> ys [B, T+1, n].
The cell's family sets n and m, its configuration the library's `order`
(`Cell.dims`); L = C(order + n + m, n + m).
"""
from __future__ import annotations

F32 = 4


def library_size(order: int, n_vars: int) -> int:
    from math import comb
    return comb(order + n_vars, n_vars)


def gru_flops(B: int, T: int, d: int, H: int) -> float:
    """Input projection, the two recurrent matmuls, gates and update."""
    per_step = 2 * d * 3 * H + 2 * H * 2 * H + 2 * H * H + 10 * H
    return float(B * T * per_step)


def gru_bytes(B: int, T: int, d: int, H: int) -> float:
    weights = d * 3 * H + H * 3 * H + 3 * H
    return float(F32 * (B * T * d + B * H + weights + B * T * H + B * H))


def rk4_flops(B: int, T: int, *, order: int, n: int, m: int) -> float:
    """Four stages of library products and theta @ Phi, then the update."""
    L = library_size(order, n + m)
    per_stage = (order - 1) * L + 2 * n * L + 2 * n
    return float(B * T * (4 * per_stage + 6 * n))


def rk4_bytes(B: int, T: int, *, order: int, n: int, m: int) -> float:
    L = library_size(order, n + m)
    return float(F32 * (B * n * L + B * n + B * T * m + B * (T + 1) * n))


def merinda_forward_flops(S: int, k: int, H: int, HH: int, *, order: int,
                          n: int, m: int) -> float:
    """One refit forward over S windows of k steps: GRU, head, RK4 decode
    and the collocation residual."""
    L = library_size(order, n + m)
    coll = (k - 1) * ((order - 1) * L + 2 * n * L)
    return (encode_flops(S, k, H, HH, order=order, n=n, m=m)
            + rk4_flops(S, k, order=order, n=n, m=m) + S * coll)


def encode_flops(S: int, k: int, H: int, HH: int, *, order: int, n: int,
                 m: int) -> float:
    """The GRU over S windows of k steps and the head to n x L
    coefficients and m input shifts."""
    L = library_size(order, n + m)
    return gru_flops(S, k, n + m, H) + S * (2 * (2 * H) * HH
                                            + 2 * HH * (n * L + m))


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peaks: dict) -> tuple[float, str] | None:
    """Share (%) of the chip's roofline, and which bound sets it."""
    if seconds <= 0 or flops <= 0:
        return None
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / seconds, (
        "compute" if t_flops >= t_bytes else "memory")

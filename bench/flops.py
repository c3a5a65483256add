"""Operations and bytes that each kernel's algorithm needs, from shapes.

Counts follow the algorithm, not the kernel's implementation: a one-hot
selection matmul that gathers library inputs costs nothing here, padding
rows cost nothing, and recomputation does not count.  A multiply-add is two
operations.  Bytes are the float32 operands read and results written once.

Shapes are those of the calls the window made (the unpadded batch):
  GRU scan  xs [B, T, d] with hidden H;
  RK4 solve theta [B, n, L], y0 [B, n], us [B, T, m] -> ys [B, T+1, n].
"""
from __future__ import annotations

N, M = 3, 1           # F-8 states and inputs
F32 = 4


def library_size(order: int = 3, n_vars: int = N + M) -> int:
    from math import comb
    return comb(order + n_vars, n_vars)


def gru_flops(B: int, T: int, d: int, H: int) -> float:
    """Input projection, the two recurrent matmuls, gates and update."""
    per_step = 2 * d * 3 * H + 2 * H * 2 * H + 2 * H * H + 10 * H
    return float(B * T * per_step)


def gru_bytes(B: int, T: int, d: int, H: int) -> float:
    weights = d * 3 * H + H * 3 * H + 3 * H
    return float(F32 * (B * T * d + B * H + weights + B * T * H + B * H))


def rk4_flops(B: int, T: int, order: int = 3, n: int = N) -> float:
    """Four stages of library products and theta @ Phi, then the update."""
    L = library_size(order, n + M)
    per_stage = (order - 1) * L + 2 * n * L + 2 * n
    return float(B * T * (4 * per_stage + 6 * n))


def rk4_bytes(B: int, T: int, order: int = 3, n: int = N, m: int = M) -> float:
    L = library_size(order, n + m)
    return float(F32 * (B * n * L + B * n + B * T * m + B * (T + 1) * n))


def merinda_forward_flops(S: int, k: int, H: int, HH: int,
                          order: int = 3) -> float:
    """One refit forward over S windows of k steps: GRU, head, RK4 decode
    and the collocation residual."""
    L = library_size(order)
    head = 2 * (2 * H) * HH + 2 * HH * (N * L + M)
    coll = (k - 1) * ((order - 1) * L + 2 * N * L)
    return (gru_flops(S, k, N + M, H) + S * head + rk4_flops(S, k, order)
            + S * coll)


def encode_flops(S: int, k: int, H: int, HH: int, order: int = 3) -> float:
    L = library_size(order)
    return gru_flops(S, k, N + M, H) + S * (2 * (2 * H) * HH
                                            + 2 * HH * (N * L + M))


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 peaks: dict) -> tuple[float, str] | None:
    """Share (%) of the chip's roofline, and which bound sets it."""
    if seconds <= 0 or flops <= 0:
        return None
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / seconds, (
        "compute" if t_flops >= t_bytes else "memory")

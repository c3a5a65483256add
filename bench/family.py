"""A physics family's published model, as its file states it.

`bench/physics/<system>.json` is the benchmark's own copy of a family's
model; a configuration names it by its `system` key.  The file holds the
state and input counts `n` and `m`, the polynomial order, the sampling
interval `dt`, the initial-state box `y0_low`/`y0_high`, the excitation
(sum-of-sines tones per input, their frequency and amplitude bands), the
coefficients as per-state lists of `[[variable indices], coefficient]`
(states 0..n-1, then inputs n..n+m-1), and the damage rule.  The one rule,
`input_effectiveness`, scales every coefficient of a term that contains an
input: a control surface that answers, but weakly (the damaged-elevator
F-8, for one).

`theta` places the coefficients into the library the server learns: all
monomials of total degree <= its order over [y0..y(n-1), u0..u(m-1)],
ordered by degree and then lexicographically.  Plain NumPy: the harness
reads a family before JAX is imported.
"""
from __future__ import annotations

import itertools
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

DAMAGE_RULES = ("input_effectiveness",)


@dataclass(frozen=True)
class Physics:
    """One family's published model, as its physics file states it."""
    name: str
    n: int
    m: int
    order: int
    dt: float
    y0_low: np.ndarray           # [n]
    y0_high: np.ndarray          # [n]
    tones: int                   # sum-of-sines tones per input
    freq_hz: tuple               # (low, high) band of each tone
    amp: tuple                   # (low, high) band of each tone's amplitude
    rows: tuple                  # per state {sorted variable tuple: coeff}
    damage: str


def load_physics(path: Path) -> Physics:
    """Read and check a physics file."""
    with open(path) as f:
        d = json.load(f)
    n, m, order = int(d["n"]), int(d["m"]), int(d["order"])
    rows = tuple({tuple(sorted(int(v) for v in term)): float(c)
                  for term, c in row} for row in d["rows"])
    bad = [t for row in rows for t in row
           if len(t) > order or any(not 0 <= v < n + m for v in t)]
    exc = d["excitation"]
    if (len(rows) != n or bad or len(d["y0_low"]) != n
            or len(d["y0_high"]) != n or d["damage"] not in DAMAGE_RULES):
        raise ValueError(
            f"{path}: expected {n} rows of terms over variables 0..{n + m - 1}"
            f" of degree <= {order}, a y0 box of {n} states and a damage "
            f"rule in {DAMAGE_RULES}; terms out of range: {bad}")
    return Physics(name=d["name"], n=n, m=m, order=order, dt=float(d["dt"]),
                   y0_low=np.asarray(d["y0_low"], np.float64),
                   y0_high=np.asarray(d["y0_high"], np.float64),
                   tones=int(exc["tones"]), freq_hz=tuple(exc["freq_hz"]),
                   amp=tuple(exc["amp"]), rows=rows, damage=d["damage"])


def monomials(n_vars: int, order: int):
    """Library terms as sorted variable tuples, by degree then lexicographic."""
    return [combo for d in range(order + 1)
            for combo in itertools.combinations_with_replacement(
                range(n_vars), d)]


def theta(physics: Physics, order: int,
          effectiveness: float = 1.0) -> np.ndarray:
    """Coefficients [n, L] in the library of degree <= `order` (at least the
    physics' own); `effectiveness` scales the terms the damage rule names."""
    p = physics
    index = {t: j for j, t in enumerate(monomials(p.n + p.m, order))}
    out = np.zeros((p.n, len(index)))
    for i, row in enumerate(p.rows):
        for term, c in row.items():
            hit = any(v >= p.n for v in term)     # input_effectiveness
            out[i, index[term]] = c * (effectiveness if hit else 1.0)
    return out

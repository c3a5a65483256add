"""F8 Crusader aircraft longitudinal dynamics (paper's primary benchmark).

The classic Garrard & Jordan polynomial model (order 3, n=3 states, m=1 input):
  y0 = angle of attack, y1 = pitch angle, y2 = pitch rate, u = elevator.

dy0/dt = -0.877 y0 + y2 - 0.088 y0*y2 + 0.47 y0^2 - 0.019 y1^2 - y0^2*y2
         + 3.846 y0^3 - 0.215 u + 0.28 y0^2*u + 0.47 y0*u^2 + 0.63 u^3
dy1/dt = y2
dy2/dt = -4.208 y0 - 0.396 y2 - 0.47 y0^2 - 3.564 y0^3
         - 20.967 u + 6.265 y0^2*u + 46 y0*u^2 + 61.4 u^3

The paper sweeps "model dimension" 20..150 on this system (Fig. 4 / Table II).
We reproduce that sweep with `F8Crusader(n_aircraft=k)`: a fleet of k
independent F8 airframes stacked into one 3k-dimensional system — the digital-
twinning deployment scenario (one twin per tracked aircraft), which scales the
state dimension exactly as the paper's x-axis does while keeping the true
dynamics sparse and identifiable.
"""
from __future__ import annotations

import dataclasses

from repro.systems.base import DynamicalSystem, SystemSpec


def _f8_rows(base: int, n: int, u_name: str) -> list[dict[str, float]]:
    """Rows for one airframe whose states are y{base}..y{base+2}."""
    a, b, q = f"y{base}", f"y{base + 1}", f"y{base + 2}"
    u = u_name

    def nm(*parts):
        return "*".join(sorted(parts))

    row0 = {
        a: -0.877, q: 1.0, nm(a, q): -0.088, nm(a, a): 0.47,
        nm(b, b): -0.019, nm(a, a, q): -1.0, nm(a, a, a): 3.846,
        u: -0.215, nm(a, a, u): 0.28, nm(a, u, u): 0.47, nm(u, u, u): 0.63,
    }
    row1 = {q: 1.0}
    row2 = {
        a: -4.208, q: -0.396, nm(a, a): -0.47, nm(a, a, a): -3.564,
        u: -20.967, nm(a, a, u): 6.265, nm(a, u, u): 46.0, nm(u, u, u): 61.4,
    }
    return [row0, row1, row2]


class F8Crusader(DynamicalSystem):
    """F8 longitudinal dynamics; `n_aircraft` stacks independent airframes.

    State dim n = 3 * n_aircraft, one shared elevator input (m=1) — the
    collision-avoidance scenario drives the fleet with a common commanded
    maneuver while each airframe's response is recovered independently.
    """

    def __init__(self, n_aircraft: int = 1):
        self.n_aircraft = n_aircraft
        n = 3 * n_aircraft
        self.spec = SystemSpec(
            name=f"f8_crusader_{n}d" if n_aircraft > 1 else "f8_crusader",
            n=n, m=1, order=3,
            dt=0.01, horizon=600,
            # the open-loop F8 cubic terms (3.846 y0^3) destabilize large
            # angle-of-attack excursions; ranges per the verification
            # literature's trim-neighbourhood studies.
            y0_low=tuple([-0.15, -0.05, -0.05] * n_aircraft),
            y0_high=tuple([0.30, 0.05, 0.05] * n_aircraft),
            input_kind="sum_of_sines", input_scale=0.05,
        )

    def rows(self):
        rows: list[dict[str, float]] = []
        u_name = f"u0"
        # note: in the library naming, inputs come after ALL states, so the
        # input name is independent of n_aircraft.
        for k in range(self.n_aircraft):
            rows.extend(_f8_rows(3 * k, self.spec.n, u_name))
        return rows


class DamagedF8(F8Crusader):
    """One F-8 airframe with partial elevator loss: every input-dependent
    coefficient is scaled by `effectiveness` — the control surface answers,
    but weakly.  The online-twinning scenarios fly it while serving the
    nominal model, so the divergence guard has a real dynamics change to
    catch."""

    def __init__(self, effectiveness: float = 0.25):
        super().__init__()
        self.effectiveness = effectiveness

    def rows(self):
        rows = _f8_rows(0, self.spec.n, "u0")
        return [{k: (v * self.effectiveness if "u0" in k else v)
                 for k, v in row.items()} for row in rows]


def trim_neighborhood(system: F8Crusader, y0_frac: float = 0.5,
                      input_scale: float = 0.03) -> F8Crusader:
    """Confine `system` to the F-8's trim neighborhood (in place; returns
    it): the open-loop cubic terms (3.846 y0^3) depart controlled flight in
    finite time for large angle-of-attack excursions, and a long open-loop
    stream from the full y0 range reliably finds that boundary for a few
    airframes."""
    system.spec = dataclasses.replace(
        system.spec,
        y0_low=tuple(v * y0_frac for v in system.spec.y0_low),
        y0_high=tuple(v * y0_frac for v in system.spec.y0_high),
        input_scale=input_scale)
    return system

"""Batched what-if rollouts with confidence bounds — the scenario engine.

The guard already rolls every deployed model forward against *observed*
telemetry; this module generalizes that machinery into the predictive
question the paper leads with: "what happens next, under inputs that have
not happened yet?"  A `ScenarioRunner` evaluates K counterfactual
action/disturbance sequences for one twin in a SINGLE fused
`rk4_poly_solve` call — the kernel folds arbitrary leading axes into its
batch axis, so an [ensemble, K] grid of rollouts costs one dispatch, not
E*K.

Confidence comes from an ENSEMBLE OVER RECENT THETAS: every deploy /
promote pushes the outgoing coefficients into a small per-twin ring
(`TwinServer._theta_hist`), and a scenario query rolls all of them forward
together.  Where the recent models agree, the envelope is tight and
confidence is ~1; where online refits have been thrashing, the envelope
widens and confidence decays toward 0.  The center trajectory is always
the LIVE theta's rollout — the bounds annotate it, they never replace it.

Deadline behavior rides the existing `DegradationPolicy` ladder: at
degradation level >= `shrink_level` the effective K deterministically
shrinks (`max(1, k // degraded_shrink)`); at >= `refuse_level` the query
is refused with `ScenarioRefused` before any device work is dispatched.
Deterministic shrink (not sampling) keeps the three server
implementations conformant under pressure — see
tests/test_service_conformance.py.

Threading: `ScenarioRunner` is stateless after construction (jit caches
aside) and safe to share across shards; `TwinServer.scenario()` must be
called from the serving thread, like `predict()`.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rk4.ops import rk4_poly_solve
from repro.obs import null_span

__all__ = [
    "ScenarioConfig", "ScenarioRefused", "ScenarioResult", "ScenarioRunner",
    "effective_k",
]

_BLOWUP = 1e6          # matches the guard's non-finite clamp (monitor.py)


@dataclass(frozen=True)
class ScenarioConfig:
    """Scenario-engine knobs (part of `TwinServerConfig`).

    max_k            hard per-query cap on counterfactual sequences
    ensemble         theta-history ring size per twin (confidence ensemble);
                     1 disables the envelope (lo == hi, confidence == 1)
    shrink_level     degradation level at which K shrinks deterministically
    degraded_shrink  divisor applied to K at shrink_level (floor 1)
    refuse_level     degradation level at which queries are refused outright
    """
    max_k: int = 32
    ensemble: int = 4
    shrink_level: int = 2
    degraded_shrink: int = 4
    refuse_level: int = 3

    def __post_init__(self):
        if self.max_k < 1 or self.ensemble < 1:
            raise ValueError("max_k and ensemble must be >= 1")
        if self.degraded_shrink < 2:
            raise ValueError("degraded_shrink must be >= 2")
        if not (0 < self.shrink_level <= self.refuse_level):
            raise ValueError("need 0 < shrink_level <= refuse_level")


class ScenarioRefused(RuntimeError):
    """Scenario query refused under deadline pressure (degradation ladder).

    Subclasses RuntimeError so callers that only handle the `predict()`
    error surface degrade gracefully; the message always starts with
    ``scenario refused`` so the federated coordinator can re-raise the
    precise type across the wire boundary.
    """


def effective_k(requested: int, level: int, cfg: ScenarioConfig) -> int:
    """Deterministic K under the degradation ladder; raises when refused."""
    if requested < 1:
        raise ValueError(f"k must be >= 1, got {requested}")
    if requested > cfg.max_k:
        raise ValueError(f"k {requested} exceeds max_k {cfg.max_k}")
    if level >= cfg.refuse_level:
        raise ScenarioRefused(
            f"scenario refused: degradation level {level} >= "
            f"refuse_level {cfg.refuse_level}")
    if level >= cfg.shrink_level:
        return max(1, requested // cfg.degraded_shrink)
    return requested


@dataclass(frozen=True)
class ScenarioResult:
    """One twin's what-if answer: K trajectories plus an uncertainty band.

    ys          [K, H+1, n] center trajectories (LIVE theta rollout)
    lo, hi      [K, H+1, n] ensemble envelope (min/max over recent thetas)
    confidence  [K] in (0, 1]: 1 / (1 + normalized ensemble spread)
    k           effective K served (may be < requested_k when degraded)
    degraded_level   degradation-ladder level at serve time
    """
    twin_id: int
    horizon: int
    requested_k: int
    k: int
    degraded_level: int
    ys: np.ndarray
    lo: np.ndarray
    hi: np.ndarray
    confidence: np.ndarray


class ScenarioRunner:
    """Fused ensemble x K rollout engine over a PolyLibrary model family.

    One runner per model configuration (library + dt + backend); shards
    with identical configs share a runner — and therefore a jit cache —
    via `share_modules_from`, exactly like the fleet model itself.  `span`
    is the building server's `Tracer.span` (shards share one tracer).
    """

    def __init__(self, library, dt: float, cfg: ScenarioConfig, *,
                 use_pallas: bool = False, interpret: bool | None = None,
                 span=null_span):
        self.span = span
        self.lib = library
        self.dt = float(dt)
        self.cfg = cfg
        self.use_pallas = bool(use_pallas)
        self.interpret = interpret
        self._roll = jax.jit(self._roll_packed)

    # ------------------------------------------------------------------ #
    def _roll_impl(self, theta_hist, count, y0, us):
        """theta_hist [E,n,L], count scalar, y0 [n], us [K,H,m] ->
        (center [K,H+1,n], lo, hi, confidence [K])."""
        E, n, L = theta_hist.shape
        K = us.shape[0]
        live_idx = jnp.maximum(count - 1, 0) % E
        live = theta_hist[live_idx]
        # unfilled ring slots fall back to the live theta: a twin with one
        # deploy still answers, with a degenerate (zero-width) envelope
        valid = jnp.arange(E) < count
        ens = jnp.where(valid[:, None, None], theta_hist, live[None])
        theta = jnp.broadcast_to(ens[:, None], (E, K, n, L))
        y0b = jnp.broadcast_to(y0[None, None], (E, K, n))
        usb = jnp.broadcast_to(us[None], (E,) + us.shape)
        ys = rk4_poly_solve(theta, y0b, usb, dt=self.dt, library=self.lib,
                            use_pallas=self.use_pallas,
                            interpret=self.interpret)
        ys = jnp.nan_to_num(ys, nan=_BLOWUP, posinf=_BLOWUP, neginf=-_BLOWUP)
        ys = jnp.clip(ys, -_BLOWUP, _BLOWUP)
        center = ys[live_idx]
        lo = ys.min(axis=0)
        hi = ys.max(axis=0)
        # normalized mean envelope width per scenario: spread measured in
        # units of the center trajectory's own scale, squashed to (0, 1]
        scale = jnp.std(center, axis=(1, 2)) + 1e-6
        spread = jnp.mean(hi - lo, axis=(1, 2)) / scale
        confidence = 1.0 / (1.0 + spread)
        return center, lo, hi, confidence

    def _roll_packed(self, theta_hist, count, y0, us):
        """`_roll_impl`'s four results raveled into one float32 array
        (center, lo, hi, then confidence), so the host reads them back in
        one transfer."""
        return jnp.concatenate(
            [x.ravel() for x in self._roll_impl(theta_hist, count, y0, us)])

    # ------------------------------------------------------------------ #
    def rollout(self, theta_hist, count: int, y0, us) -> tuple:
        """Device entry point; shapes as `_roll_impl`. Blocks on the result
        (host arrays out — scenario answers leave the device anyway): one
        program, which takes `us` and `count` straight from the host, and
        one read back in a `sync` span, split into the four arrays."""
        us = np.asarray(us, np.float32)
        if us.ndim != 3:
            raise ValueError(f"us must be [K, H, m], got {us.shape}")
        packed = self._roll(theta_hist, np.int32(count), y0, us)
        with self.span("sync", site="scenario.result"):
            packed = np.asarray(packed)
        K, H = us.shape[:2]
        shape = (K, H + 1, np.shape(y0)[-1])
        size = K * (H + 1) * shape[2]
        center, lo, hi = (packed[i * size:(i + 1) * size].reshape(shape)
                          for i in range(3))
        return center, lo, hi, packed[3 * size:]

"""Fleet digital twinning: many independent MERINDA instances on one mesh.

The paper's deployment scenario is mission-critical online twinning (mid-air
collision avoidance): every tracked aircraft gets its own continuously-refit
digital twin.  At production scale that is thousands of CONCURRENT model
recoveries — an embarrassingly parallel, latency-critical workload.

`FleetMerinda` vmaps a Merinda instance over a fleet axis (separate params,
separate data per twin) and exposes:
  * fleet_init / fleet_step  — one fused training step for every twin
    (the latency-critical fused step; examples/fleet_twinning.py),
  * recover_all              — batched model extraction,
  * reset_slot               — re-initialize ONE fleet slot in place,
    without touching the other twins,
  * reset_slots              — the online-serving admission path: every
    slot twin/scheduler.py admits in one tick, reset in one fixed-shape
    program (sequential `reset_slot`s with sequential key splits).

Online serving (twin/server.py) treats the fleet axis as a bounded pool of
REFIT SLOTS: twins are admitted/evicted dynamically, so per-slot training
progress must be tracked per slot — `state["steps"]` carries one step counter
per slot and the sparsify warmup (`FleetConfig.sparsify_after`) is applied
slot-wise, not globally.

Sharding: the fleet axis is sharded over ('pod','data') and the GRU/head
matmuls over 'model' via the rules in distributed/sharding.py, so one
train_step advances every twin on the pod simultaneously.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.merinda import Merinda, MerindaConfig
from repro.distributed.sharding import shard
from repro.train.optimizer import adamw, apply_updates, clip_by_global_norm

__all__ = ["FleetConfig", "FleetMerinda"]


@dataclass(frozen=True)
class FleetConfig:
    merinda: MerindaConfig
    fleet: int                  # number of concurrent twins (refit slots)
    windows_per_twin: int = 32  # S_B per twin per step
    lr: float = 3e-3
    sparsify_after: int = 200   # per-slot warmup steps before the hard top-k mask
    grad_clip: float = 1.0      # per-twin gradient clip


class FleetMerinda:
    def __init__(self, cfg: FleetConfig):
        self.cfg = cfg
        self.model = Merinda(cfg.merinda)
        # clipping happens PER TWIN inside _twin_grad: a global clip would
        # couple twins through the norm, and a single twin's non-finite
        # gradient would poison every slot in the fleet.
        self.opt = adamw(lr=cfg.lr, clip_norm=None)

    # ------------------------------------------------------------------ #
    def init(self, key):
        keys = jax.random.split(key, self.cfg.fleet)
        params = jax.vmap(self.model.init)(keys)
        opt_state = self.opt.init(params)   # leaves carry the fleet axis
        return {"params": params, "opt": opt_state,
                "step": jnp.zeros((), jnp.int32),
                "steps": jnp.zeros((self.cfg.fleet,), jnp.int32)}

    # ------------------------------------------------------------------ #
    def _twin_grad(self, params, y_win, u_win, sparsify):
        (loss, aux), grads = jax.value_and_grad(self.model.loss, has_aux=True)(
            params, (y_win, u_win), sparsify)
        grads, _ = clip_by_global_norm(grads, self.cfg.grad_clip)
        # Live telemetry can hand a twin a window its current theta integrates
        # to overflow; skip that twin's step (zero grads) instead of letting
        # NaNs reach its params — the slot stays recoverable.
        ok = jnp.isfinite(loss)
        for g in jax.tree.leaves(grads):
            ok = ok & jnp.all(jnp.isfinite(g))
        grads = jax.tree.map(
            lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads)
        return jnp.where(ok, loss, 0.0), ok, grads

    @partial(jax.jit, static_argnames=("self",))
    def train_step_per_slot(self, state, y_win, u_win):
        """One fused step for every twin, with per-slot diagnostics.

        y_win: [F, S_B, k+1, n], u_win: [F, S_B, k, m] — per-twin windows.
        The sparsify warmup is evaluated PER SLOT: twins admitted into a slot
        mid-stream (steps reset by `reset_slot`) train dense until their own
        counter passes `sparsify_after`, independent of their neighbours.
        Returns (state, loss [F], ok [F]) — per-slot losses (0 where the
        step was skipped as non-finite) so the serving layer can report
        losses for assigned slots without an extra forward pass.
        """
        # logical twin_* shardings (distributed/sharding.py): the fleet axis
        # is data-parallel over ('pod','data'); no-op outside axis_rules
        y_win = shard(y_win, "twin_windows")
        u_win = shard(u_win, "twin_windows")
        sparsify = state["steps"] > self.cfg.sparsify_after      # [F] bool
        loss, ok, grads = jax.vmap(self._twin_grad)(
            state["params"], y_win, u_win, sparsify)
        updates, opt = self.opt.update(grads, state["opt"], state["params"])
        params = apply_updates(state["params"], updates)
        return ({"params": params, "opt": opt, "step": state["step"] + 1,
                 "steps": state["steps"] + 1},
                shard(loss, "twin_fleet"), ok)

    def train_step(self, state, y_win, u_win):
        """One fused step for every twin; returns the mean loss over twins
        whose step was finite (thin host-side wrapper, same compiled core)."""
        state, loss, ok = self.train_step_per_slot(state, y_win, u_win)
        return state, jnp.sum(loss) / jnp.maximum(jnp.sum(ok), 1)

    # ------------------------------------------------------------------ #
    @partial(jax.jit, static_argnames=("self",))
    def reset_slot(self, state, slot, key, y_win=None, u_win=None):
        """Re-initialize fleet slot `slot` in place (admission of a new twin).

        slot may be a traced int32 scalar, so one compiled trace serves every
        slot.  When the admitted twin's windows are provided, the slot's norm
        stats (mu/sigma/phi_scale) are computed from them — the same
        conditioning `Merinda.init` gets in the offline path.  Optimizer
        moments for the slot are zeroed; the shared Adam bias-correction step
        is left global (a warm counter only slightly damps a fresh slot's
        first updates).
        """
        norm = None
        if y_win is not None:
            norm = self.model.norm_stats(y_win, u_win)
        fresh = self.model.init(key, norm)
        params = jax.tree.map(
            lambda a, f: a.at[slot].set(f.astype(a.dtype)),
            state["params"], fresh)
        opt = state["opt"]
        opt = opt._replace(
            mu=jax.tree.map(lambda a: a.at[slot].set(0.0), opt.mu),
            nu=jax.tree.map(lambda a: a.at[slot].set(0.0), opt.nu))
        return {"params": params, "opt": opt, "step": state["step"],
                "steps": state["steps"].at[slot].set(0)}

    @partial(jax.jit, static_argnames=("self",))
    def reset_slots(self, state, slots, key, y_win, u_win):
        """`reset_slot` for every admission of a tick, in one program.

        slots: [R] int32, the admitted slots in admission order, padded
        with -1 after the last one; y_win [R, N, k+1, n] and u_win
        [R, N, k, m] are the admitted twins' windows (rows past the last
        admission are ignored).  Admission i draws `key, sub = split(key)`
        and resets `slots[i]` from `sub` and its windows, exactly as a host
        loop of `reset_slot` calls would, so the fixed shape compiles once
        and the loop runs only as many times as there are admissions.
        Returns (state, key) with the key advanced past every draw.
        """
        def admit(i, carry):
            state, key = carry
            key, sub = jax.random.split(key)
            return (self.reset_slot(state, slots[i], sub, y_win[i],
                                    u_win[i]), key)
        return jax.lax.fori_loop(0, jnp.sum(slots >= 0), admit, (state, key))

    # ------------------------------------------------------------------ #
    @partial(jax.jit, static_argnames=("self",))
    def recover_all(self, state, y_win, u_win):
        """Batched model extraction (no polish — pure in-network path, the
        latency-critical deployment call)."""
        def one(p, y, u):
            theta_dense, _ = self.model.encode(p, y, u)
            pooled = jnp.median(theta_dense, axis=0, keepdims=True)
            return self.model.sparsify(pooled, True,
                                       p["norm"]["phi_scale"])[0]
        return jax.vmap(one)(state["params"], y_win, u_win)

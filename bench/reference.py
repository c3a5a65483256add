"""The plain reference, and the comparison that decides `correct`.

Straightforward `jax.numpy` in float32, written from the published
description of each stage and from the configuration file, importing
nothing of the program:

  * the F-8 library and a classic RK4 rollout of dY/dt = theta @ Phi(Y, u)
    with zero-order-hold inputs;
  * the guard score: mean squared rollout error over the window, divided by
    the window's variance plus 1e-6, non-finite scores clamped to 1e6;
  * the MERINDA refit step: a GRU encoder over the normalised windows, a
    ReLU head to library coefficients and an input shift, a top-n_active
    straight-through mask once a slot has trained past `sparsify_after`,
    the RK4 rollout, the ODE + L1 + collocation loss, per-twin gradient
    clipping with non-finite steps skipped, then AdamW;
  * the what-if rollout over the recent-theta ensemble, its envelope and
    confidence.

Each matmul runs at `highest` (full float32, what the configuration
states) or, for the control, at `high`: three bfloat16 passes, written out
so that it means the same on every backend.

The inputs are the benchmark's own telemetry, windowed by the reference,
and the program's model state where the program owns it (the served thetas,
the refit slots' parameters and optimizer state).  Every telemetry window
the program gathered is also compared with the benchmark's own, exactly.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.telemetry import monomials

_BLOWUP = 1e6
_HIGHEST = jax.lax.Precision.HIGHEST


def _split(x):
    """x = hi + lo + rest, hi and lo bfloat16 numbers.  `reduce_precision`
    and not a round trip through bfloat16, which XLA may drop as excess
    precision."""
    def bf16(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    hi = bf16(x)
    return hi, bf16(x - hi)


def mm(spec: str, a, b, mode: str):
    """einsum at full float32 ('highest') or in three bf16 passes ('high')."""
    if mode == "highest":
        return jnp.einsum(spec, a, b, precision=_HIGHEST)
    ah, al = _split(a)
    bh, bl = _split(b)
    e = partial(jnp.einsum, spec, precision=_HIGHEST)
    return e(ah, bh) + e(ah, bl) + e(al, bh)


def features(y, u):
    """Library Phi [..., 35] of y [..., 3], u [..., 1]."""
    x = jnp.concatenate([y, u], axis=-1)
    one = jnp.ones(x.shape[:-1], x.dtype)
    return jnp.stack([jnp.prod(x[..., list(t)], axis=-1) if t else one
                      for t in monomials()], axis=-1)


def rk4(theta, y0, us, dt: float, mode: str):
    """theta [B, 3, L], y0 [B, 3], us [B, T, 1] -> ys [B, T+1, 3]."""
    def rhs(y, u):
        return mm("bnl,bl->bn", theta, features(y, u), mode)

    def step(y, u):
        k1 = rhs(y, u)
        k2 = rhs(y + 0.5 * dt * k1, u)
        k3 = rhs(y + 0.5 * dt * k2, u)
        k4 = rhs(y + dt * k3, u)
        y = y + (dt / 6.0) * (k1 + 2.0 * k2 + 2.0 * k3 + k4)
        return y, y

    _, ys = jax.lax.scan(step, y0, jnp.swapaxes(us, 0, 1))
    return jnp.concatenate([y0[:, None], jnp.swapaxes(ys, 0, 1)], axis=1)


@partial(jax.jit, static_argnames=("dt", "mode"))
def guard_scores(theta, ys, us, *, dt: float, mode: str):
    y_est = rk4(theta, ys[:, 0], us, dt, mode)
    num = jnp.mean(jnp.square(y_est - ys), axis=(1, 2))
    den = jnp.mean(jnp.square(ys - jnp.mean(ys, axis=1, keepdims=True)),
                   axis=(1, 2)) + 1e-6
    return jnp.nan_to_num(num / den, nan=_BLOWUP, posinf=_BLOWUP)


# --------------------------------------------------------------------------- #
def _gru(xs, p, mode):
    """xs [S, T, d] -> (hs [S, T, H], hT [S, H]); gates [z | r | c]."""
    H = p["wh"].shape[0]
    xp = mm("std,dg->stg", xs, p["wx"], mode) + p["b"]

    def step(h, xt):
        hp = mm("sh,hg->sg", h, p["wh"][:, :2 * H], mode)
        z = jax.nn.sigmoid(xt[:, :H] + hp[:, :H])
        r = jax.nn.sigmoid(xt[:, H:2 * H] + hp[:, H:])
        c = jnp.tanh(xt[:, 2 * H:] + mm("sh,hg->sg", r * h,
                                        p["wh"][:, 2 * H:], mode))
        h = (1.0 - z) * h + z * c
        return h, h

    h0 = jnp.zeros((xs.shape[0], H), xs.dtype)
    hT, hs = jax.lax.scan(step, h0, jnp.swapaxes(xp, 0, 1))
    return jnp.swapaxes(hs, 0, 1), hT


def _encode(p, y_win, u_win, mcfg, mode):
    n, m = y_win.shape[-1], u_win.shape[-1]
    L = len(monomials())
    norm = jax.lax.stop_gradient(p["norm"])
    xs = jnp.concatenate([y_win[:, :-1], u_win], axis=-1)
    xs = (xs - norm["mu"]) / norm["sigma"]
    hs, hT = _gru(xs, p["gru"], mode)
    summary = jnp.concatenate([hT, hs.mean(axis=1)], axis=-1)
    hd = p["head"]
    h = jax.nn.relu(mm("sa,ab->sb", summary, hd["w1"], mode) + hd["b1"])
    raw = (mm("sa,ab->sb", h, hd["w2"], mode) + hd["b2"]) \
        * mcfg["theta_scale"]
    theta_dense = raw[:, :n * L].reshape(-1, n, L) / norm["phi_scale"]
    return theta_dense, raw[:, n * L:n * L + m]


def _mask_top(theta_dense, phi_scale, n_active):
    S, n, L = theta_dense.shape
    flat = theta_dense.reshape(S, n * L)
    k = min(n_active, n * L)
    mag = jax.lax.stop_gradient(jnp.abs(flat * jnp.tile(phi_scale, n)))
    thresh = jnp.sort(mag, axis=-1)[:, -k][:, None]
    return (flat * (mag >= thresh)).reshape(S, n, L)


def _loss(p, y_win, u_win, sparsify, mcfg, mode):
    dt = mcfg["dt"]
    theta_dense, shift = _encode(p, y_win, u_win, mcfg, mode)
    phi_scale = jax.lax.stop_gradient(p["norm"]["phi_scale"])
    theta = jnp.where(sparsify,
                      _mask_top(theta_dense, p["norm"]["phi_scale"],
                                mcfg["n_active"]), theta_dense)
    y_est = rk4(theta, y_win[:, 0], u_win + shift[:, None, :], dt, mode)
    loss = jnp.mean(jnp.square(y_est - y_win))
    l1 = jnp.mean(jnp.abs(theta_dense * phi_scale))
    loss = loss + jnp.where(sparsify, 0.1 * mcfg["l1"], mcfg["l1"]) * l1
    dy = (y_win[:, 2:] - y_win[:, :-2]) / (2.0 * dt)
    pred = mm("snl,skl->skn", theta,
              features(y_win[:, 1:-1], u_win[:, 1:]), mode)
    return loss + mcfg["collocation_weight"] * jnp.mean(jnp.square(pred - dy))


@partial(jax.jit, static_argnames=("mode", "mkey", "rkey"))
def refit_step(params, opt, steps, y_win, u_win, *, mode, mkey, rkey):
    """One fused refit step over every slot -> (params', mu', nu', loss [F],
    grads [F])."""
    mcfg, rcfg = dict(mkey), dict(rkey)
    sparsify = steps > rcfg["sparsify_after"]

    def one(p, y, u, sp):
        loss, g = jax.value_and_grad(_loss)(p, y, u, sp, mcfg, mode)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                            for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, rcfg["grad_clip"] / (norm + 1e-9)), g)
        ok = jnp.isfinite(loss)
        for x in jax.tree.leaves(g):
            ok = ok & jnp.all(jnp.isfinite(x))
        g = jax.tree.map(lambda x: jnp.where(ok, x, 0.0), g)
        return jnp.where(ok, loss, 0.0), g

    loss, g = jax.vmap(one)(params, y_win, u_win, sparsify)
    b1, b2, eps, lr = (rcfg["adam_b1"], rcfg["adam_b2"], rcfg["adam_eps"],
                       rcfg["lr"])
    t = (opt["step"] + 1).astype(jnp.float32)
    mu = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, opt["mu"], g)
    nu = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * jnp.square(x),
                      opt["nu"], g)
    new = jax.tree.map(
        lambda p, a, v: p - lr * (a / (1 - b1 ** t))
        / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, mu, nu)
    return new, loss, g


@partial(jax.jit, static_argnames=("dt", "mode"))
def scenario(theta_hist, count, y0, us, *, dt: float, mode: str):
    """(centre [K, H+1, n], low, high, confidence [K], peak [K], growth
    [K]): `peak` is the largest state any ensemble member reaches under
    each input sequence, before the clamp (not finite where a rollout
    overflowed); `growth` the most by which any member's rollout magnifies
    a small nudge of the initial state (1: not at all), which is how much
    it magnifies rounding."""
    E, n, L = theta_hist.shape
    K, H, m = us.shape
    live = jnp.maximum(count - 1, 0) % E
    valid = jnp.arange(E) < count
    ens = jnp.where(valid[:, None, None], theta_hist, theta_hist[live][None])
    theta = jnp.broadcast_to(ens[:, None], (E, K, n, L)).reshape(E * K, n, L)
    u = jnp.broadcast_to(us[None], (E, K, H, m)).reshape(E * K, H, m)
    nudge = 1e-4 * jnp.maximum(jnp.max(jnp.abs(y0)), 1e-2)
    y0s = jnp.stack([y0, y0 + nudge * (-1.0) ** jnp.arange(n)])
    both = rk4(jnp.concatenate([theta, theta]),
               jnp.repeat(y0s, E * K, axis=0), jnp.concatenate([u, u]),
               dt, mode).reshape(2, E, K, H + 1, n)
    ys = both[0]
    peak = jnp.max(jnp.abs(ys), axis=(0, 2, 3))
    growth = jnp.max(jnp.abs(both[1] - ys), axis=(0, 2, 3)) / nudge
    ys = jnp.clip(jnp.nan_to_num(ys, nan=_BLOWUP, posinf=_BLOWUP,
                                 neginf=-_BLOWUP), -_BLOWUP, _BLOWUP)
    center = ys[live]
    lo, hi = ys.min(axis=0), ys.max(axis=0)
    spread = jnp.mean(hi - lo, axis=(1, 2)) / (jnp.std(center, axis=(1, 2))
                                               + 1e-6)
    return center, lo, hi, 1.0 / (1.0 + spread), peak, growth


# --------------------------------------------------------------------------- #
def _np(tree):
    return jax.tree.map(np.asarray, tree)


def _params(state) -> dict:
    """The program's fleet state as plain nested dicts of arrays."""
    opt = state["opt"]
    return {"params": state["params"], "steps": state["steps"],
            "opt": {"step": opt.step, "mu": opt.mu, "nu": opt.nu}}


class Windows:
    """The reference's own telemetry windows, from the benchmark's stream."""

    def __init__(self, traffic, srv):
        self.ys, self.us = traffic.ys, traffic.us
        self.chunk = traffic.chunk
        shards = list(getattr(srv, "shards", [srv]))
        self.row2twin = [{rec.ring_slot: tid for tid, rec in s.twins.items()}
                         for s in shards]

    def twin(self, shard: int, row: int):
        return self.row2twin[shard].get(int(row))

    def latest(self, twin: int, loop: int, length: int):
        """Newest length+1 samples as the ring holds them after `loop`."""
        S = (loop + 1) * self.chunk
        return self.ys[twin, S - length - 1:S], self.us[twin,
                                                       S - length - 1:S - 1]

    def slot_windows(self, twin: int, loop: int, window: int, stride: int,
                     count: int):
        span = stride * (count - 1) + window
        ys, us = self.latest(twin, loop, span)
        y = np.stack([ys[s:s + window + 1] for s in range(0, span - window + 1,
                                                         stride)])
        u = np.stack([us[s:s + window] for s in range(0, span - window + 1,
                                                      stride)])
        return y, u


def _rel(a, b, floor):
    return np.abs(a - b) / np.maximum(np.abs(b), floor)


def _leaf_norms(tree, sel):
    return [float(np.linalg.norm(np.asarray(x)[sel]))
            for x in jax.tree.leaves(tree)]


def _worst_leaf(got, ref, moved):
    """Largest gap between the program's and the reference's norm of a leaf,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = float(np.median([r for r, m in zip(ref, moved) if m]))
    return max((abs(a - b) / max(b, med, 1e-30)
                for a, b, m in zip(got, ref, moved) if m), default=0.0)


def check(cell, traffic, srv, rec, *, stand_in: str | None = None,
          detail: list | None = None) -> dict:
    """Compare the kept calls with the reference; returns
    {name: {"value", "limit"}}.

    `stand_in` puts something else in the program's place: 'high', the
    control (the reference at 'high'), or 'half', a refit step that leaves
    out half of each slot's windows and takes the mean over the rest (the
    reference at 'highest' on that half).  A `detail` list receives, per
    call, the numbers each gap was taken from (for `bench/calibrate.py`).
    Only the numbers a cell's limits file names are compared; the others
    are read for `calibrate.py` alone.
    """
    note = detail.append if detail is not None else (lambda d: None)
    cfg = cell.config
    mc = dict(cfg["merinda"])
    sc = cfg["server_config"]
    rc = cfg["refit"]
    mkey = tuple(sorted({"dt": mc["dt"], "n_active": mc["n_active"],
                         "l1": mc["l1"], "theta_scale": mc["theta_scale"],
                         "collocation_weight": mc["collocation_weight"]
                         }.items()))
    rkey = tuple(sorted({"sparsify_after": sc["sparsify_after"],
                         "lr": sc["lr"], **rc}.items()))
    dt = mc["dt"]
    high = stand_in == "high"
    scratch = sc.get("max_twins", cfg["twins"])
    win = Windows(traffic, srv)
    gaps = {"ring": [0.0], "guard": [], "loss": [], "grad": [], "step": [],
            "scenario": []}

    for k in rec.kept:
        kind, loop = k["kind"], k["loop"]
        if kind == "guard":
            rows = np.asarray(k["rows"])
            live = [(i, win.twin(k["shard"], r)) for i, r in enumerate(rows)
                    if r != scratch]
            live = [(i, t) for i, t in live if t is not None]
            if not live:
                continue
            idx = np.asarray([i for i, _ in live])
            T = k["us"].shape[1]
            ys_b, us_b = zip(*(win.latest(t, loop, T) for _, t in live))
            ys_b, us_b = np.stack(ys_b), np.stack(us_b)
            ys_p = np.asarray(k["ys"])[idx]
            us_p = np.asarray(k["us"])[idx]
            gaps["ring"].append(float(max(np.max(np.abs(ys_p - ys_b)),
                                          np.max(np.abs(us_p - us_b)))))
            theta = jnp.asarray(np.asarray(k["theta"])[idx])
            ref = np.asarray(guard_scores(theta, ys_b, us_b, dt=dt,
                                          mode="highest"))
            got = (np.asarray(guard_scores(theta, ys_b, us_b, dt=dt,
                                           mode="high")) if high
                   else np.asarray(k["out"])[idx])
            # a score over the alert threshold (1: the rollout's error
            # exceeds the window's own variance, as for a candidate model
            # barely trained) comes from a rollout that left the data, where
            # rounding is amplified without bound; such twins are left out
            fit = ref <= 1.0
            if not fit.any():
                continue
            got, ref = got[fit], ref[fit]
            # relative L2 over the call's twins: the largest relative gap
            # of one twin is set by the smallest scores (a model that fits
            # to the noise), where rounding moves the score most
            gaps["guard"].append(float(np.linalg.norm(got - ref)
                                       / max(np.linalg.norm(ref), 1e-30)))
            note({"kind": kind, "loop": loop, "gap": gaps["guard"][-1],
                  "worst_twin": float(np.max(_rel(got, ref, 1e-9)))})
        elif kind == "step":
            rows = np.asarray(k["rows"])
            slots = [(s, win.twin(k["shard"], r)) for s, r in enumerate(rows)
                     if r != scratch]
            slots = [(s, t) for s, t in slots if t is not None]
            if not slots:
                continue
            y_p, u_p = np.asarray(k["y_win"]), np.asarray(k["u_win"])
            y_b, u_b = y_p.copy(), u_p.copy()
            for s, t in slots:
                y_b[s], u_b[s] = win.slot_windows(
                    t, loop, sc["window"], sc["stride"], y_p.shape[1])
            sel = np.asarray([s for s, _ in slots])
            gaps["ring"].append(float(max(np.max(np.abs(y_p - y_b)[sel]),
                                          np.max(np.abs(u_p - u_b)[sel]))))
            st = _params(k["state"])
            new_r, loss_r, g_r = refit_step(
                st["params"], st["opt"], st["steps"], y_b, u_b,
                mode="highest", mkey=mkey, rkey=rkey)
            loss_r = np.asarray(loss_r)
            # a slot whose reference rollout left the flight envelope (loss
            # over 1, an RMS error of a radian) or was skipped as non-finite
            # amplifies rounding without bound; it is left out
            sel = sel[(loss_r[sel] > 0) & (loss_r[sel] <= 1.0)]
            if not len(sel):
                continue
            if stand_in is not None:
                half = y_b.shape[1] // 2 if stand_in == "half" else None
                new_p, loss_p, g_p = refit_step(
                    st["params"], st["opt"], st["steps"], y_b[:, :half],
                    u_b[:, :half], mode="high" if high else "highest",
                    mkey=mkey, rkey=rkey)
            else:
                out_state, loss_p, _ = k["out"]
                new_p = out_state["params"]
                # the gradient as the optimizer got it, from its first moment
                b1 = rc["adam_b1"]
                g_p = jax.tree.map(
                    lambda a, b: (np.asarray(a) - b1 * np.asarray(b))
                    / (1 - b1), out_state["opt"].mu, st["opt"]["mu"])
            loss_p = np.asarray(loss_p)
            gaps["loss"].append(float(np.max(_rel(loss_p[sel], loss_r[sel],
                                                  1e-6))))
            old = _np(st["params"])
            d_r = jax.tree.map(lambda a, b: np.asarray(a) - b, new_r, old)
            d_p = jax.tree.map(lambda a, b: np.asarray(a) - b, new_p, old)
            g_n = _leaf_norms(g_r, sel)
            # leaves whose reference gradient is under a thousandth of the
            # median leaf's move by round-off alone, and are left out
            moved = [g >= 1e-3 * float(np.median(g_n)) for g in g_n]
            gaps["grad"].append(_worst_leaf(_leaf_norms(g_p, sel), g_n, moved))
            gaps["step"].append(_worst_leaf(_leaf_norms(d_p, sel),
                                            _leaf_norms(d_r, sel), moved))
            note({"kind": kind, "loop": loop, "loss": gaps["loss"][-1],
                  "grad": gaps["grad"][-1], "step": gaps["step"][-1],
                  "steps": np.asarray(st["steps"])[sel].tolist(),
                  "loss_p": loss_p[sel].tolist(),
                  "loss_r": loss_r[sel].tolist()})
        elif kind == "scenario":
            y0_b = win.latest(k["twin"], loop, 0)[0][-1]
            gaps["ring"].append(float(np.max(np.abs(k["y0"] - y0_b))))
            args = (jnp.asarray(k["theta_hist"]), jnp.int32(k["count"]),
                    jnp.asarray(y0_b), jnp.asarray(k["us"]))
            *ref, peak, growth = [np.asarray(x) for x in scenario(
                *args, dt=dt, mode="highest")]
            got = ([np.asarray(x) for x in scenario(*args, dt=dt,
                                                    mode="high")]
                   if high else [np.asarray(x) for x in k["out"]])
            # an input sequence under which some member of the ensemble
            # leaves the flight envelope (a state over 1 rad or rad/s, as a
            # freshly promoted model may diverge), or magnifies a nudge of
            # its initial state more than tenfold, magnifies rounding as
            # much in the centre and the envelope; it is left out
            fly = np.isfinite(peak) & (peak <= 1.0) & (growth <= 10.0)
            if not fly.any():
                continue
            # centre, low and high, against the largest state among them
            ref3, got3 = np.stack(ref[:3])[:, fly], np.stack(got[:3])[:, fly]
            worst = float(np.max(np.abs(got3 - ref3))
                          / max(float(np.max(np.abs(ref3))), 1e-2))
            gaps["scenario"].append(worst)
            note({"kind": kind, "loop": loop, "gap": worst,
                  "left_out": int(np.sum(~fly)),
                  "growth": float(np.max(growth[fly])),
                  "ref_max": float(np.max(np.abs(ref3)))})

    out = {}
    for name, vals in gaps.items():
        if name not in cell.limits:
            continue
        if not vals:
            raise RuntimeError(f"the check found no {name} call to compare")
        out[name] = {"value": float(max(vals)),
                     "limit": float(cell.limits[name])}
    return out

"""Plain reference of the campus conditioner, interval by interval.

Each controller interval of k samples (per rack, racks independent):

1. render the rack power r_t (``render.py``);
2. hardware path, sample by sample (paper Eq. 2 and Fig. 5): the ESS ramp
   filter g += a (r - g) with a = 1 - exp(-beta dt); battery power
   p = clip(g - r + c_t, -p_max, p_max), where the corrective command c_t
   slews linearly from the last applied command to this interval's target;
   state of charge integrates p with the charge/discharge efficiencies and
   sheds what would cross the safe window; the node draws r + p through
   the discretised LC filter, whose busbar current is the grid power;
3. battery wear: a turning-point machine over the SoC samples (a reversal
   closes a half-cycle), plus per-interval sums of the SoC moves;
4. controller: the BMS estimate soc_ema moves by min(dt_ctrl / tau, 1)
   toward the interval's final SoC; the QP of Eqs. 13-17 is solved by
   ``qp_iters`` ADMM iterations warm-started from the last interval; the
   first action c_0 - d_0, clipped to the current limit and zeroed inside
   the deadband, is the next slew target.

The stream predicts no idle window, so the outer loop holds S_mid.

``dtype`` and ``precision`` set the arithmetic: float32 with matrix
products at HIGHEST is the configuration's; the control runs bfloat16 and
HIGH.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import plant, render as R

HIGHEST = jax.lax.Precision.HIGHEST
WEAR = ("prev_soc", "last_ext", "direction", "half_cycles", "cycle_damage",
        "max_dod", "charge_soc", "discharge_soc", "soc_sum", "soc_sq_sum",
        "samples")


class Reference:
    def __init__(self, dep, *, dtype=jnp.float32, precision=HIGHEST):
        self.dep = dep
        self.dtype = dtype
        fi = jnp.finfo(dtype)
        # Round to ``dtype`` explicitly: XLA may keep a float32 value
        # through a float32 -> bfloat16 -> float32 round trip (excess
        # precision), which would leave the control above its precision.
        self.round = lambda x: jax.lax.reduce_precision(x, fi.nexp, fi.nmant)
        self.precision = precision
        p = dep.pdu
        ad, bd, c = plant.discrete_filter(p["rack"], p["f_f_hz"], dep.dt)
        self.ad, self.bd, self.c = ad, bd, c
        self.ess = plant.ess(p["rack"], p["grid"], p)
        self.qp = plant.qp(p["controller"], self.ess)
        self.wear = plant.health(p["health"]) if p["track_health"] else None
        self.ctrl = p["controller"]
        self.k = dep.k
        self.n_campus = len(dep.campuses)
        self.r = dep.campuses[0].n_racks
        # The deployment's data rides into the compiled programs as
        # arguments, so that every seed runs the same programs.
        self.data = (
            {key: jnp.asarray(np.stack([c.cols[key] for c in dep.campuses]))
             for key in dep.campuses[0].cols},
            jnp.asarray(np.asarray([c.salt for c in dep.campuses], np.uint32)),
        )
        self._calls = {}

    # ------------------------------------------------------------ render
    def render(self, data, t0, n):
        """(n, C * R) rack power, campuses side by side."""
        dep = self.dep
        one = functools.partial(
            R.render, t0=t0, n=n, dt=dep.dt, total=dep.total_samples,
            w=dep.edge_width, pad=dep.edge_pad, noise_seed=dep.noise_seed)
        tr = jax.vmap(one)(*data)  # (C, n, R)
        return jnp.transpose(tr, (1, 0, 2)).reshape(n, -1)

    # ------------------------------------------------------------- state
    def initial_state(self) -> dict:
        r0 = np.asarray(jax.jit(lambda d: self.render(d, jnp.int32(0), 1))(self.data),
                        np.float64)[0]
        m = np.linalg.inv(np.eye(3) - self.ad)
        x0 = (m @ (self.bd[:, :1] + self.bd[:, 1:] * r0[None, :])).T  # (N, 3)
        n = r0.shape[0]
        soc0 = float(self.dep.pdu["soc0"])
        h = self.qp["h"]
        f = lambda v: jnp.full((n,), v, jnp.float32)
        st = {
            "filter_state": jnp.asarray(x0, jnp.float32),
            "g_filter": jnp.asarray(r0, jnp.float32),
            "soc": f(soc0), "u_prev": f(0.0), "cmd_applied": f(0.0),
            "cmd_target": f(0.0), "soc_ema": f(soc0),
            "wx": jnp.zeros((2 * h, n), jnp.float32),
            "wz": jnp.zeros((3 * h, n), jnp.float32),
            "wy": jnp.zeros((3 * h, n), jnp.float32),
        }
        for name in WEAR:
            st["h_" + name] = f(soc0) if name in ("prev_soc", "last_ext") else f(0.0)
        st["h_samples"] = jnp.zeros((n,), jnp.int32)
        return st

    # ---------------------------------------------------------- interval
    def _interval(self, st, t0, data):
        k, dt, dtype = self.k, self.dep.dt, self.dtype
        e, q = self.ess, self.qp
        cst = lambda v: jnp.asarray(v, dtype)
        rack = self.round(self.render(data, t0, k)).astype(dtype)  # (k, N)
        alpha = cst(1.0 - jnp.exp(-jnp.float32(e["beta"]) * dt))
        ad, bl, bv, crow = (cst(self.ad), cst(self.bd[:, 1]), cst(self.bd[:, 0]), cst(self.c))
        ramp = jnp.arange(1, k + 1, dtype=jnp.float32) / k
        applied = st["cmd_applied"].astype(dtype)
        diff = (st["cmd_target"] - st["cmd_applied"]).astype(dtype)
        wear = self.wear

        def step(carry, xs):
            g, soc, x, hw = carry
            r, rp = xs
            c_t = applied + diff * rp.astype(dtype)
            g_new = g + alpha * (r - g)
            p = jnp.clip(g_new - r + c_t, -cst(e["p_max"]), cst(e["p_max"]))
            charge, dis = jnp.maximum(p, 0), jnp.maximum(-p, 0)
            s = soc + cst(dt / e["q_max"]) * (cst(e["eta_c"]) * charge - dis / cst(e["eta_d"]))
            over_hi = jnp.maximum(s - cst(e["soc_max"]), 0)
            over_lo = jnp.maximum(cst(e["soc_min"]) - s, 0)
            p = (p - over_hi * cst(e["q_max"] / (e["eta_c"] * dt))
                 + over_lo * cst(e["q_max"] * e["eta_d"] / dt))
            s = jnp.clip(s, cst(e["soc_min"]), cst(e["soc_max"]))
            node = r + p
            # Elementwise products: a TPU's default matrix product would
            # round float32 operands to bfloat16.
            y = crow[0] * x[:, 0] + crow[1] * x[:, 1] + crow[2] * x[:, 2]
            x_new = jnp.stack([
                ad[i, 0] * x[:, 0] + ad[i, 1] * x[:, 1] + ad[i, 2] * x[:, 2]
                + bl[i] * node + bv[i] for i in range(3)], axis=-1)
            if wear is not None:
                last_ext, direction, half, dmg, maxdod = hw
                d = s - soc
                sd = jnp.where(d > wear["eps"], 1.0, jnp.where(d < -wear["eps"], -1.0, 0.0)).astype(dtype)
                rev = (sd * direction) < 0
                revf = rev.astype(dtype)
                depth = jnp.abs(soc - last_ext)
                half_w = jnp.maximum(cst(wear["c0"]) + cst(wear["c1"]) * (soc + last_ext), 0)
                kappa = wear["kappa"]
                powd = depth ** (int(kappa) if float(kappa).is_integer() else kappa)
                hw = (jnp.where(rev, soc, last_ext), jnp.where(sd != 0, sd, direction),
                      half + revf, dmg + revf * half_w * powd,
                      jnp.maximum(maxdod, revf * depth))
            return (g_new, s, x_new, hw), (y, s)

        hw0 = tuple(st["h_" + n].astype(dtype) for n in WEAR[1:6]) if wear else ()
        carry0 = (st["g_filter"].astype(dtype), st["soc"].astype(dtype),
                  st["filter_state"].astype(dtype), hw0)
        (g, soc, x, hw), (grid, soc_t) = jax.lax.scan(step, carry0, (rack, ramp))
        f32 = lambda a: a.astype(jnp.float32)
        g, soc, x, grid, soc_t = f32(g), f32(soc), f32(x), f32(grid), f32(soc_t)
        new = dict(st, g_filter=g, soc=soc, filter_state=x)
        if wear is not None:
            for n, v in zip(WEAR[1:6], hw):
                new["h_" + n] = f32(v)
            prev_t = jnp.concatenate([st["soc"][None], soc_t[:-1]], axis=0)
            delta = soc_t - prev_t
            new["h_prev_soc"] = soc
            new["h_charge_soc"] = st["h_charge_soc"] + jnp.sum(jnp.maximum(delta, 0.0), axis=0)
            new["h_discharge_soc"] = st["h_discharge_soc"] + jnp.sum(jnp.maximum(-delta, 0.0), axis=0)
            new["h_soc_sum"] = st["h_soc_sum"] + jnp.sum(soc_t, axis=0)
            new["h_soc_sq_sum"] = st["h_soc_sq_sum"] + jnp.sum(soc_t * soc_t, axis=0)
            new["h_samples"] = st["h_samples"] + jnp.int32(k)

        # Controller: BMS estimate, warm-started ADMM, first action.
        ctrl = self.ctrl
        mm = functools.partial(jnp.matmul, precision=self.precision)
        meas_w = min(float(ctrl["dt"]) / float(ctrl["meas_tau"]), 1.0)
        soc_meas = st["soc_ema"] + meas_w * (soc - st["soc_ema"])
        tgt = jnp.float32(ctrl["s_mid"])
        m32 = lambda a: jnp.asarray(a, jnp.float32)
        a_mat, kinv, kinv_at = m32(q["a"]), m32(q["kinv"]), m32(q["kinv_at"])
        e0 = (soc_meas - tgt) / jnp.float32(q["ds_ref"])
        qv = m32(q["q_e0"])[:, None] * e0[None] + m32(q["q_du"])[:, None] * st["u_prev"][None]
        lo = m32(q["lo"])[:, None] - m32(q["soc_rows"])[:, None] * soc_meas[None]
        hi = m32(q["hi"])[:, None] - m32(q["soc_rows"])[:, None] * soc_meas[None]

        def admm(carry, _):
            xq, z, y = carry
            xq = mm(kinv, plant.SIGMA * xq - qv + mm(a_mat.T, plant.RHO * z - y))
            ax = mm(a_mat, xq)
            z = jnp.clip(ax + y / plant.RHO, lo, hi)
            y = y + plant.RHO * (ax - z)
            return (xq, z, y), None

        (xq, z, y), _ = jax.lax.scan(
            admm, (st["wx"], st["wz"], st["wy"]), None, length=self.dep.qp_iters)
        ax = mm(a_mat, xq)
        resid = jnp.max(jnp.abs(ax - jnp.clip(ax, lo, hi)), axis=0)
        h, imax = q["h"], jnp.float32(ctrl["i_max"])
        i0 = jnp.clip(xq[0] - xq[h], -imax, imax)
        i0 = jnp.where(jnp.abs(soc_meas - tgt) <= jnp.float32(ctrl["deadband"]), 0.0, i0)
        new.update(u_prev=i0 / imax, cmd_applied=st["cmd_target"], cmd_target=i0,
                   soc_ema=soc_meas, wx=xq, wz=z, wy=y)
        cmean = lambda a: self.round(jnp.mean(
            a.reshape(a.shape[0], self.n_campus, self.r), axis=-1).astype(jnp.float32))
        out = {
            "campus_rack": cmean(rack),  # (k, C)
            "campus_grid": cmean(grid),
            "soc_mean": cmean(soc[None])[0],  # (C,)
            "qp_residual": jnp.max(resid.reshape(self.n_campus, self.r), axis=-1),
        }
        return new, out

    def _call_fn(self, n_int):
        fn = self._calls.get(n_int)
        if fn is None:
            @jax.jit
            def fn(st, t0, data):
                def body(st, i):
                    return self._interval(st, t0 + i * self.k, data)
                return jax.lax.scan(body, st, jnp.arange(n_int, dtype=jnp.int32))
            self._calls[n_int] = fn
        return fn

    def run(self, n_calls: int, call_samples: int) -> list:
        """Per-call outputs of the first ``n_calls`` calls of the stream,
        each ``call_samples`` long, from the initial state."""
        if call_samples % self.k:
            raise ValueError("a call covers whole controller intervals")
        fn = self._call_fn(call_samples // self.k)
        st = self.initial_state()
        outs = []
        for j in range(n_calls):
            st, o = fn(st, jnp.int32(j * call_samples), self.data)
            outs.append((o, st))
        return [self._host(o, st) for o, st in outs]

    def _host(self, o, st) -> dict:
        c, r = self.n_campus, self.r
        t = lambda a: np.asarray(a).T.reshape(c, -1)
        out = {
            "campus_rack": t(np.asarray(o["campus_rack"]).reshape(-1, c)),
            "campus_grid": t(np.asarray(o["campus_grid"]).reshape(-1, c)),
            "soc_mean": np.asarray(o["soc_mean"]).T,
            "qp_residual": float(np.max(np.asarray(o["qp_residual"]))),
        }
        names = ("filter_state", "g_filter", "soc", "soc_ema")
        out["state"] = {n: np.asarray(st[n]).reshape((c, r) + np.shape(st[n])[1:])
                        for n in names}
        if self.wear is not None:
            for n in WEAR:
                out["state"]["h_" + n] = np.asarray(st["h_" + n]).reshape(c, r)
        return out

    def wear_snapshot(self, state: dict) -> np.ndarray:
        """(C, 3) [mean equivalent full cycles, max capacity fade, max
        closed half-cycle depth] of a per-call state."""
        w = self.wear
        if w is None:
            return np.zeros((self.n_campus, 3))
        s = {k[2:]: np.asarray(v, np.float64) for k, v in state.items() if k.startswith("h_")}
        t = s["samples"] * self.dep.dt
        cal = np.maximum(t + w["cal_soc_gain"] * (s["soc_sum"] * self.dep.dt - w["soc_ref"] * t),
                         0.0) / w["calendar_life_s"]
        fade = w["eol_fade"] * (s["cycle_damage"] / w["n_cycles_ref"] + cal)
        efc = 0.5 * (s["charge_soc"] + s["discharge_soc"])
        return np.stack([efc.mean(-1), fade.max(-1), s["max_dod"].max(-1)], axis=-1)

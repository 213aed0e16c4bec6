"""Interval-resident conditioning megakernel + batched-ADMM kernel parity.

Runs the Pallas kernels in interpret mode against their jnp oracles
(``ref.pdu_health_sim`` / ``ref.admm_iterate``) through the ``ops``
dispatch layer, pinning the PR-5 reproducibility contract:

* SoC path, ESS filter value and **every** health leaf: bitwise.
* Grid / LC filter state: bitwise against the jitted reference; a few
  ulp against the eagerly evaluated one on some interval lengths (XLA
  contracts the LC mul-add chain into FMAs differently — see the kernel
  docstring).
* Degraded-mode weights w in {0, 1}: bitwise against the same masked
  reference path the engines run.
* The turning-point machine and block accumulators: bitwise under
  stream splits (kernel-of-halves == kernel-of-whole == reference).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import controller as ctrl, health as hlt, pdu
from repro.core.ess import ESSParams
from repro.kernels import ops, pdu_health, ref
from repro.power import faults as flt, scenario as SC

pytestmark = pytest.mark.pallas

R, HZ = 192, 200.0


def _setup(t, n_racks=R):
    s = SC.mixed_campus(
        n_racks, ("llama3_2_1b", "deepseek_v3_671b"),
        duration_s=30.0, sample_hz=HZ, seed=3, noise_seed=2,
    )
    chunk = jax.jit(lambda: SC.render(s, 0, t))()
    cfg = pdu.make_pdu(sample_dt=1.0 / HZ, track_health=True)
    st = pdu.init_state(cfg, chunk[0])
    ep = cfg.ess_params
    kw = dict(
        beta=float(ep.beta), dt=1.0 / HZ, q_max=float(ep.q_max),
        eta_c=float(ep.eta_c), eta_d=float(ep.eta_d), p_max=float(ep.p_max),
        soc_min=float(ep.soc_safe_min), soc_max=float(ep.soc_safe_max),
    )
    filt = st.filter_obj
    args = (st.ess_state.g_filter, st.ess_state.soc, st.filter_state,
            filt.ad, filt.bd, filt.c[0])
    health = (hlt.step_consts(cfg.health), tuple(st.health))
    return chunk, args, kw, health


def _slew(n_racks=R):
    applied = jnp.zeros((n_racks,), jnp.float32)
    target = 0.01 * jnp.ones((n_racks,), jnp.float32)
    return applied, target


def _bw(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def _assert_parity(r_ref, r_pl, *, grid_bitwise):
    grid_r, soc_r, (g_r, socf_r, x_r), h_r = r_ref
    grid_p, soc_p, (g_p, socf_p, x_p), h_p = r_pl
    assert _bw(soc_r, soc_p), "SoC path must be bitwise"
    assert _bw(g_r, g_p), "ESS filter final must be bitwise"
    assert _bw(socf_r, socf_p), "SoC final must be bitwise"
    if grid_bitwise:
        assert _bw(grid_r, grid_p), "grid must be bitwise on aligned intervals"
        assert _bw(x_r, x_p)
    else:
        np.testing.assert_allclose(
            np.asarray(grid_p), np.asarray(grid_r), rtol=0, atol=1e-5
        )
        np.testing.assert_allclose(np.asarray(x_p), np.asarray(x_r), rtol=0, atol=1e-5)
    if h_r is None:
        assert h_p is None
    else:
        for i, (a, b) in enumerate(zip(h_r, h_p)):
            assert _bw(a, b), f"health leaf {i} must be bitwise"


# ------------------------------------------------------------- megakernel


def test_unmasked_parity_bitwise():
    chunk, args, kw, health = _setup(40)
    r1 = ref.pdu_health_sim(*([chunk] + list(args)), slew=_slew(), health=health, **kw)
    r2 = ops.pdu_health_sim(
        *([chunk] + list(args)), slew=_slew(), health=health, force="pallas", **kw
    )
    _assert_parity(r1, r2, grid_bitwise=True)


def test_masked_binary_weights_bitwise():
    """w in {0, 1} (hard converter cutoff) — the degraded-mode contract."""
    chunk, args, kw, health = _setup(40)
    w = (jax.random.uniform(jax.random.key(7), (R,)) > 0.3).astype(jnp.float32)
    r1 = ref.pdu_health_sim(
        *([chunk] + list(args)), slew=_slew(), ess_on=w, health=health, **kw
    )
    r2 = ops.pdu_health_sim(
        *([chunk] + list(args)), slew=_slew(), ess_on=w, health=health,
        force="pallas", **kw
    )
    _assert_parity(r1, r2, grid_bitwise=True)


def test_fractional_winddown_weights_bitwise():
    """Per-sample fractional weights (converter wind-down ramp, 2-D path)."""
    chunk, args, kw, health = _setup(40)
    w = jnp.clip(jax.random.uniform(jax.random.key(8), (40, R)), 0.0, 1.0)
    r1 = ref.pdu_health_sim(
        *([chunk] + list(args)), slew=_slew(), ess_on=w, health=health, **kw
    )
    r2 = ops.pdu_health_sim(
        *([chunk] + list(args)), slew=_slew(), ess_on=w, health=health,
        force="pallas", **kw
    )
    _assert_parity(r1, r2, grid_bitwise=True)


def test_dense_and_scalar_corrective_parity():
    chunk, args, kw, health = _setup(40)
    corr = 0.02 * jax.random.normal(jax.random.key(9), (40, R), jnp.float32)
    for c in (corr, 0.0):
        r1 = ref.pdu_health_sim(*([chunk] + list(args)), corrective=c, health=health, **kw)
        r2 = ops.pdu_health_sim(
            *([chunk] + list(args)), corrective=c, health=health, force="pallas", **kw
        )
        _assert_parity(r1, r2, grid_bitwise=True)


def test_ragged_final_interval():
    """t = 37, a prime interval: the loop must stop at t, padding must
    never leak into the block reductions, and against the eager reference
    the contract degrades only on the grid/LC path (ulp; see kernel
    docstring)."""
    chunk, args, kw, health = _setup(37)
    r1 = ref.pdu_health_sim(*([chunk] + list(args)), slew=_slew(), health=health, **kw)
    r2 = ops.pdu_health_sim(
        *([chunk] + list(args)), slew=_slew(), health=health, force="pallas", **kw
    )
    _assert_parity(r1, r2, grid_bitwise=False)


def _jit_ref(*args, health=None, **kw):
    """The reference as the engines run it: jitted, so XLA fuses the
    health epilogue's square-and-sum as it does the kernel wrapper's.
    (Evaluated eagerly, op by op, that sum rounds differently from the
    fused one for intervals of 32 samples or fewer.)"""
    fn = functools.partial(ref.pdu_health_sim, health=health, **kw)
    return jax.jit(fn)(*args)


def _events(t, n_racks, n_ev=3, i0=5):
    """Sorted, non-overlapping (E, R) episode tables, a base row, and an
    absolute index window whose last real sample clamps two steps early."""
    kg, kl, kb = jax.random.split(jax.random.key(10), 3)
    gaps = jax.random.randint(kg, (n_ev, n_racks), 0, 9)
    lens = jax.random.randint(kl, (n_ev, n_racks), 1, 7)
    ends = jnp.cumsum(gaps + lens, axis=0)
    base = (jax.random.uniform(kb, (n_racks,)) > 0.2).astype(jnp.float32)
    return (ends - lens, ends, base, jnp.int32(i0), jnp.int32(i0 + t - 3))


def _variant_kw(variant, t, n_racks):
    """Keyword arguments of one megakernel variant (ref and kernel alike)."""
    if variant == "unmasked":
        return dict(slew=_slew(n_racks))
    if variant == "mask_2d":
        return dict(slew=_slew(n_racks), ess_on=jax.random.uniform(
            jax.random.key(8), (t, n_racks)))
    if variant == "ess_events":
        return dict(slew=_slew(n_racks), ess_events=_events(t, n_racks), ess_edge=4)
    if variant == "dense_corrective":
        return dict(corrective=0.02 * jax.random.normal(
            jax.random.key(9), (t, n_racks), jnp.float32))
    raise ValueError(variant)


@pytest.mark.parametrize("n_racks", [100, 1000, 2100])
@pytest.mark.parametrize(
    "variant", ["unmasked", "mask_2d", "ess_events", "dense_corrective", "no_health"])
def test_sublane_tiling_parity(variant, n_racks):
    """Racks fill (s, 128) tiles per time step: one partial group (100),
    eight groups with ragged lanes (1000), 17 groups padded to a 24-row
    block (2100); t = 21 is ragged against every sublane count.  Every
    variant stays bitwise against the jitted reference, grid included."""
    t = 21
    chunk, args, kw, health = _setup(t, n_racks)
    if variant == "no_health":
        health, vkw = None, _variant_kw("unmasked", t, n_racks)
    else:
        vkw = _variant_kw(variant, t, n_racks)
    r1 = _jit_ref(chunk, *args, health=health, **vkw, **kw)
    r2 = ops.pdu_health_sim(chunk, *args, health=health, force="pallas", **vkw, **kw)
    _assert_parity(r1, r2, grid_bitwise=True)


# Rows of the slew + health variant, and of its episode-table form at the
# fault process's episode cap (two tables of E rows and a base row more).
_ROWS = 24
_MAX_ROWS = _ROWS + 2 * flt.MAX_EPISODES + 1


@pytest.mark.parametrize("n_racks,n_rows,s,g_pad", [
    (1, _ROWS, 1, 1), (128, _ROWS, 1, 1), (129, _ROWS, 2, 2),
    (1000, _ROWS, 8, 8), (1024, _ROWS, 8, 8), (1025, _ROWS, 16, 16),
    (2100, _ROWS, 24, 24), (4000, _ROWS, 32, 32), (4200, _ROWS, 24, 48),
    (100, _MAX_ROWS, 1, 1), (1024, _MAX_ROWS, 8, 8), (4000, _MAX_ROWS, 8, 32),
    (4000, _MAX_ROWS + 2000, 8, 32),
])
def test_tiling_rule(n_racks, n_rows, s, g_pad):
    """s follows the shape alone: one group keeps time on the sublanes of a
    (tc, 128) block, up to 8 groups take the full group count, more take
    up to four (8, 128) vregs per block, spread evenly over the fewest
    blocks, and fewer when the row operands would fill half the VMEM
    budget.  Every block fits the default scoped VMEM up to the episode
    cap; beyond it the limit is raised to what the blocks need."""
    got_s, got_g, tc, n_t, limit = pdu_health._tiling(1000, n_racks, 3, n_rows)
    assert (got_s, got_g) == (s, g_pad)
    assert n_t * tc >= 1000 > (n_t - 1) * tc
    tile_rows = -(-s // 8) * 8
    step = 4 * 128 * (tile_rows if s > 1 else 1)
    if s == 1:
        assert tc % 8 == 0
    need = 2 * n_rows * 4 * 128 * tile_rows + 2 * 3 * step * tc
    assert (limit is None) == (n_rows <= _MAX_ROWS)
    assert need <= (limit or pdu_health._BLOCK_VMEM)


@pytest.mark.parametrize("n_racks,budget,tiling", [
    # 33 groups in two 24-row rack blocks, 15 rows padded; chunks of 11
    # and a ragged 10.
    (4200, 2 * _ROWS * 4 * 128 * 24 + 2 * 3 * 11 * 4 * 128 * 24, (24, 48, 11, 2)),
    # One group, time on the sublanes: chunks of 16 and a ragged 5.
    (100, 2 * _ROWS * 4 * 128 * 8 + 2 * 3 * 16 * 4 * 128 + 4096, (1, 1, 16, 2)),
])
def test_multi_tile_and_rack_padding(monkeypatch, n_racks, budget, tiling):
    """With the VMEM budget shrunk, t = 21 runs as two time chunks with a
    ragged last one, over rack blocks or the one-group layout.  Tiling
    must not change a single bit."""
    t = 21
    monkeypatch.setattr(pdu_health, "_BLOCK_VMEM", budget)
    assert pdu_health._tiling(t, n_racks, 3, _ROWS) == (*tiling, None)
    pdu_health.pdu_health_sim.clear_cache()
    try:
        chunk, args, kw, health = _setup(t, n_racks)
        r1 = _jit_ref(chunk, *args, slew=_slew(n_racks), health=health, **kw)
        r2 = ops.pdu_health_sim(
            chunk, *args, slew=_slew(n_racks), health=health, force="pallas", **kw
        )
        _assert_parity(r1, r2, grid_bitwise=True)
    finally:
        pdu_health.pdu_health_sim.clear_cache()


def test_no_health_path():
    chunk, args, kw, _ = _setup(40)
    r1 = ref.pdu_health_sim(*([chunk] + list(args)), slew=_slew(), **kw)
    r2 = ops.pdu_health_sim(
        *([chunk] + list(args)), slew=_slew(), force="pallas", **kw
    )
    _assert_parity(r1, r2, grid_bitwise=True)


def test_stream_split_health_bitwise():
    """The PR-5 split-invariance contract, now for the megakernel: the
    turning-point machine carries (prev, last_ext, direction, half_cycles,
    cycle_damage, max_dod) and the sample count are bit-identical under
    ANY stream split; the block-reduction leaves (charge/discharge
    throughput, SoC sums) are bit-identical whenever both sides fold the
    same blocks — so kernel-chain == reference-chain bitwise on every
    leaf, and kernel-chain == one-shot bitwise on the machine leaves."""
    t = 40
    chunk, args, kw, health = _setup(t)
    g0, soc0, x0, ad, bd, c_row = args
    hc, h0 = health
    MACHINE = (0, 1, 2, 3, 4, 5, 10)

    one = ops.pdu_health_sim(
        chunk, g0, soc0, x0, ad, bd, c_row, slew=_slew(), health=(hc, h0),
        force="pallas", **kw
    )
    for cut in (8, 17, 32):
        # The slew ramp is interval-scoped, so splitting mid-interval
        # replays the same rendered corrective profile via the dense path.
        applied, target = _slew()
        ramp = jnp.arange(1, t + 1, dtype=jnp.float32) / t
        corr = applied + (target - applied) * ramp[:, None]

        def chain(fn, force=None):
            fkw = {} if force is None else {"force": force}
            _, _, (gf, sf, xf), ha = fn(
                chunk[:cut], g0, soc0, x0, ad, bd, c_row,
                corrective=corr[:cut], health=(hc, h0), **fkw, **kw
            )
            return fn(
                chunk[cut:], gf, sf, xf, ad, bd, c_row,
                corrective=corr[cut:], health=(hc, ha), **fkw, **kw
            )

        _, _, fin_k, hk = chain(ops.pdu_health_sim, force="pallas")
        _, _, _, hr = chain(ref.pdu_health_sim)
        for i, (x, y) in enumerate(zip(hk, hr)):
            assert _bw(x, y), f"cut={cut}: health leaf {i} drifts vs ref chain"
        for i in MACHINE:
            assert _bw(hk[i], one[3][i]), (
                f"cut={cut}: machine leaf {i} drifts vs one-shot"
            )
        assert _bw(fin_k[1], one[2][1])


# ------------------------------------------------------------ batched ADMM


def _plan_problem(n_racks=R, seed=0):
    cfg, es = ctrl.ControllerConfig.create(), ESSParams.create(q_max_seconds=40.0)
    plan = ctrl.make_plan(cfg, es)
    k1, k2 = jax.random.split(jax.random.key(seed))
    soc = jnp.clip(0.5 + 0.2 * jax.random.normal(k1, (n_racks,)), 0.15, 0.85)
    u_prev = 0.3 * jax.random.normal(k2, (n_racks,))
    q, lo, hi = ctrl._qp_state_terms(plan, soc, jnp.float32(0.5), u_prev)
    kq = plan.kkt_inv @ q
    x0 = jnp.zeros_like(q)
    z0 = jnp.clip(plan.a_mat @ x0, lo, hi)
    y0 = jnp.zeros_like(z0)
    kkt_stack = jnp.concatenate([plan.kkt_inv_sigma, plan.kkt_inv_at], axis=1)
    g_blk = plan.a_mat[2 * plan.horizon:]
    return plan, (kkt_stack, g_blk, kq, lo, hi, x0, z0, y0)


@pytest.mark.parametrize("iters", [1, 8, 30])
def test_admm_kernel_matches_reference(iters):
    """Real (contractive) controller plan: the kernel tracks the jnp
    reference through the whole loop — convergent ADMM damps the ulp-level
    FMA differences instead of amplifying them."""
    plan, ops_args = _plan_problem()
    r1 = ref.admm_iterate(*ops_args, rho=plan.rho, iters=iters)
    r2 = ops.admm_iterate(*ops_args, rho=plan.rho, iters=iters, force="pallas")
    for nm, a, b in zip("xzy", r1, r2):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=0, atol=2e-5,
            err_msg=f"{nm} after {iters} iters",
        )


def test_admm_kernel_rack_tiling():
    """Rack padding / multiple lane tiles must not change the solve."""
    plan, ops_args = _plan_problem(n_racks=300)
    r1 = ref.admm_iterate(*ops_args, rho=plan.rho, iters=20)
    r2 = ops.admm_iterate(
        *ops_args, rho=plan.rho, iters=20, force="pallas", r_blk=128
    )
    for a, b in zip(r1, r2):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0, atol=2e-5)


def test_admm_kernel_unbatched_falls_back():
    """1-D (single-rack) solves take the reference path through ops."""
    plan, (kkt_stack, g_blk, kq, lo, hi, x0, z0, y0) = _plan_problem(n_racks=1)
    args1 = (kkt_stack, g_blk, kq[:, 0], lo[:, 0], hi[:, 0], x0[:, 0], z0[:, 0], y0[:, 0])
    r1 = ref.admm_iterate(*args1, rho=plan.rho, iters=10)
    r2 = ops.admm_iterate(*args1, rho=plan.rho, iters=10, force="pallas")
    for a, b in zip(r1, r2):
        assert _bw(a, b)

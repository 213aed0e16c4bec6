"""TPU v5e compile rehearsals for the main-path Pallas kernels.

Compiles the interval megakernel (``kernels/pdu_health.py``) and the
batched-ADMM kernel (``kernels/admm_step.py``) for a described, not
attached, v5e chip at the fleet design point: k = 1000 samples per
controller interval, R = 1024 racks (one (8, 128) rack tile) and, for
the megakernel, R = 4000 (the benchmark campus: 32 groups of 128 racks,
padded from 4000) and R = 100 (one group: time on the sublanes), f32;
the episode-table variant also at the fault process's episode cap and
past it.  Nothing runs; the TPU compiler refuses here what interpret
mode cannot see (unaligned slices, VMEM over budget, unsupported
lowerings), and each kernel must come out as a Mosaic
``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import controller as ctrl, health as hlt, pdu
from repro.kernels import admm_step, pdu_health
from repro.power import faults as flt

K, R, HZ = 1000, 1024, 200.0
H, ITERS = 12, 30
N_EVENTS = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep these off the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(x, sharding):
    x = jnp.asarray(x)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _megakernel_args(variant, sharding, n_racks, n_events=N_EVENTS):
    cfg = pdu.make_pdu(sample_dt=1.0 / HZ, track_health=True)
    st = pdu.init_state(cfg, jnp.full((n_racks,), 0.5, jnp.float32))
    ep, filt = cfg.ess_params, st.filter_obj
    spec = lambda x: _spec(x, sharding)
    args = (
        jax.ShapeDtypeStruct((K, n_racks), jnp.float32, sharding=sharding),
        spec(st.ess_state.g_filter), spec(st.ess_state.soc),
        spec(st.filter_state), spec(filt.ad), spec(filt.bd), spec(filt.c[0]),
    )
    kw = dict(
        beta=float(ep.beta), dt=1.0 / HZ, q_max=float(ep.q_max),
        eta_c=float(ep.eta_c), eta_d=float(ep.eta_d), p_max=float(ep.p_max),
        soc_min=float(ep.soc_safe_min), soc_max=float(ep.soc_safe_max),
    )
    if variant == "slew_health":
        kw["slew"] = (spec(st.cmd_applied), spec(st.cmd_target))
        kw["health_consts"] = hlt.step_consts(cfg.health)
        kw["health_state"] = tuple(spec(x) for x in st.health)
    elif variant == "ess_events":
        table = jax.ShapeDtypeStruct((n_events, n_racks), jnp.int32, sharding=sharding)
        idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
        kw["slew"] = (spec(st.cmd_applied), spec(st.cmd_target))
        kw["ess_events"] = (table, table, spec(st.ess_online), idx, idx)
        kw["ess_edge"] = 7
    elif variant == "ess_on_2d":
        kw["slew"] = (spec(st.cmd_applied), spec(st.cmd_target))
        kw["ess_on"] = jax.ShapeDtypeStruct((K, n_racks), jnp.float32, sharding=sharding)
    else:
        raise ValueError(variant)
    return args, kw


@pytest.mark.parametrize("variant,n_racks,n_events", [
    pytest.param(v, n, N_EVENTS, id=v if n == R else f"{v}-{n}")
    for n in (R, 4000, 100) for v in ("slew_health", "ess_events", "ess_on_2d")
] + [
    # Episode tables at the fault process's cap fill half the block VMEM;
    # 8192 racks take several rack blocks, whose rows are double-buffered.
    pytest.param("ess_events", n, flt.MAX_EPISODES, id=f"ess_events-{n}-max_episodes")
    for n in (R, 4000, 8192)
] + [
    # An explicit episode count past the cap: the tables alone fill more
    # than three quarters of the budget, so the kernel asks for more VMEM.
    pytest.param(
        "ess_events", 8192, 2 * flt.MAX_EPISODES, id="ess_events-8192-2x_max_episodes"),
])
def test_megakernel_compiles_for_v5e(one_chip, variant, n_racks, n_events):
    args, kw = _megakernel_args(variant, one_chip, n_racks, n_events)
    compiled = pdu_health.pdu_health_sim.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # Two (k, R) f32 outputs at least; the program fits one chip's 16 GB.
    assert mem.output_size_in_bytes >= 2 * K * n_racks * 4
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_admm_kernel_compiles_for_v5e(one_chip):
    plan = ctrl.make_plan(
        ctrl.ControllerConfig.create(), pdu.make_pdu(sample_dt=1.0 / HZ).ess_params)
    assert plan.horizon == H
    kkt_stack = np.concatenate([plan.kkt_inv_sigma, plan.kkt_inv_at], axis=1)
    g_blk = np.asarray(plan.a_mat)[2 * H:]
    two_h = jax.ShapeDtypeStruct((2 * H, R), jnp.float32, sharding=one_chip)
    three_h = jax.ShapeDtypeStruct((3 * H, R), jnp.float32, sharding=one_chip)
    compiled = admm_step.admm_iterate.lower(
        _spec(kkt_stack, one_chip), _spec(g_blk, one_chip),
        two_h, three_h, three_h, two_h, three_h, three_h,
        rho=plan.rho, iters=ITERS,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()

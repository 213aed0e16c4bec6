"""The program's tracing: host spans and device scopes.

A scope is only HLO metadata, so nothing at run time shows whether it is
there: these tests look for each one where the compiler keeps it, in the
``op_name`` of the compiled scanned engine's ops, so that a refactor that
drops one fails here.
"""
import re

import jax
import jax.numpy as jnp

from repro.core import compliance, fleet, pdu, profiling
from repro.power import scenario as SC

_HZ = 200.0


def _scanned_engine_hlo() -> str:
    scen = SC.mixed_campus(4, ("llama3_2_1b",), duration_s=20.0, sample_hz=_HZ, seed=1)
    cfg = pdu.make_pdu(sample_dt=1.0 / _HZ, track_health=True)
    k = int(round(float(cfg.controller.dt) * _HZ))
    chunk = 2 * k
    n_full, rem = divmod(scen.total_samples, chunk)
    bank = fleet._make_bank(compliance.GridSpec.create(), cfg, scen.total_samples)
    run = fleet._scanned_engine(cfg, 10, chunk, k, n_full, rem, None, "data", bank)
    state = pdu.init_state(cfg, SC.render(scen, 0, 1)[0])
    return run.lower(scen, state, jnp.asarray(0, jnp.int32)).compile().as_text()


def test_scanned_engine_ops_carry_the_scopes():
    paths = set(re.findall(r'op_name="([^"]*)"', _scanned_engine_hlo()))
    for scope in ("render", "observers", "controller"):
        tag = profiling.PREFIX + scope
        inside = [p for p in paths if tag in p.split("/")]
        assert inside, f"no op of the compiled engine is in {tag}"
        # The scopes sit inside the engine's chunk scan.
        assert any("/while/body/" in p for p in inside), inside[:3]


def test_names_carry_the_program_prefix():
    with profiling.span("x") as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)

    def f(x):
        with profiling.scope("render"):
            return jnp.sin(x)

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "repro.render" in text

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


# ------------------------------------------------------------ grid region

_REGION_SCOPES = ("poi", "render", "observers", "controller")


def _region_engine_paths(n_campuses: int, mesh) -> tuple:
    """op_name paths of the compiled region engine (one campus per shard
    of ``mesh``) and of the sequential oracle's POI fold."""
    from repro.core import grid

    hz = 20.0
    reg = grid.checkpoint_region(n_campuses, 4, duration_s=25.0, sample_hz=hz)
    cfg = pdu.make_pdu(sample_dt=1.0 / hz, track_health=True)
    spec = compliance.GridSpec.create()
    k = int(round(float(cfg.controller.dt) * hz))
    chunk = 2 * k
    n_full, rem = divmod(reg.total_samples, chunk)
    assert n_full and rem  # both the scanned chunks and the remainder
    bank = fleet._make_bank(spec, cfg, reg.total_samples)
    mbank = grid.mode_bank(reg.total_samples, cfg.sample_dt, reg.bands)
    run = grid._region_engine(cfg, 10, chunk, k, n_full, rem, mesh, bank, mbank)
    state = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *(pdu.init_state(cfg, jnp.ones((4,))) for _ in range(n_campuses)))
    text = run.lower(grid._stack_campuses(reg, mesh), state, reg.weights,
                     jnp.asarray(0, jnp.int32)).compile().as_text()
    fold = grid._poi_fold(bank, mbank, chunk, n_full, rem, cfg.sample_dt)
    trace = jnp.ones((reg.total_samples,), jnp.float32)
    fold_text = fold.lower(trace, trace).compile().as_text()
    paths = lambda t: sorted(set(re.findall(r'op_name="([^"]*)"', t)))
    return paths(text), paths(fold_text)


def _scoped(paths, scope):
    tag = profiling.PREFIX + scope
    return [p for p in paths if tag in p.split("/")]


def _record_spans(monkeypatch) -> list:
    import contextlib

    names = []

    @contextlib.contextmanager
    def span(name):
        names.append(name)
        yield

    monkeypatch.setattr(profiling, "span", span)
    return names


def test_sequential_region_engine_ops_carry_the_scopes():
    from repro.core import grid

    engine, fold = _region_engine_paths(1, grid._oracle_mesh())
    for scope in _REGION_SCOPES:
        inside = _scoped(engine, scope)
        assert any("/while/body/" in p for p in inside), (scope, inside[:3])
    # The oracle folds the POI observers under the same name, in its scan
    # and in the remainder.
    inside = _scoped(fold, "poi")
    assert any("/while/body/" in p for p in inside), inside[:3]
    assert any("/while/" not in p for p in inside), inside[:3]


def test_a_region_call_fires_the_facade_spans(monkeypatch):
    from repro.core import grid

    names = _record_spans(monkeypatch)
    hz = 20.0
    reg = grid.checkpoint_region(2, 4, duration_s=20.0, sample_hz=hz)
    cfg = pdu.make_pdu(sample_dt=1.0 / hz, track_health=True)
    res = fleet.condition(reg, cfg)  # the sequential oracle
    jax.block_until_ready(res.poi_grid)
    # Each campus runs the engine's facade phases in turn, its finish
    # inside the region's.
    assert names[0] == "condition"
    assert names[1:] == ["prepare", "engine", "region_finish", "finish"] * 2, names


def test_swing_model_ops_carry_the_swing_scope():
    from repro.core import grid

    trace = jnp.linspace(0.0, 1.0, 64, dtype=jnp.float32)
    text = jax.jit(grid.poi_response, static_argnums=(1, 2)).lower(
        trace, grid.POIConfig(), 0.05).compile().as_text()
    paths = sorted(set(re.findall(r'op_name="([^"]*)"', text)))
    inside = _scoped(paths, "swing")
    assert any("/while/body/" in p for p in inside), paths


_SHARDED_SCRIPT = r"""
import contextlib, json, sys
sys.path[:0] = [{tests!r}, {src!r}]
import jax
import test_profiling as t
from repro.core import fleet, grid, pdu, profiling
from repro.sharding import rules

engine, _ = t._region_engine_paths(4, rules.region_mesh(4))
names = []

@contextlib.contextmanager
def span(name):
    names.append(name)
    yield

profiling.span = span
hz = 20.0
reg = grid.checkpoint_region(4, 4, duration_s=20.0, sample_hz=hz)
cfg = pdu.make_pdu(sample_dt=1.0 / hz, track_health=True)
jax.block_until_ready(fleet.condition(reg, cfg, mesh=rules.region_mesh(4)).poi_grid)
print(json.dumps({{"paths": engine, "spans": names}}))
"""


def test_sharded_region_engine_carries_the_scopes_and_spans_on_four_devices():
    import json
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.abspath(__file__))
    script = _SHARDED_SCRIPT.format(tests=here, src=os.path.join(here, "..", "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    for scope in _REGION_SCOPES:
        inside = _scoped(got["paths"], scope)
        assert any("/shard_map/while/body/" in p for p in inside), (scope, inside[:3])
    # The POI's cross-chip sum is folded inside the scope.
    assert any(p.endswith("/repro.poi/psum") for p in got["paths"])
    # One engine dispatch for the region; then the region's finish with
    # each campus's inside it.
    assert got["spans"] == (["condition", "prepare", "engine", "region_finish"]
                            + ["finish"] * 4), got["spans"]

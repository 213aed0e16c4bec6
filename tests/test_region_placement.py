"""Where the sharded region keeps each campus (``grid.condition_region_sharded``).

Campus ``c``'s results and carried state stay on campus ``c``'s device of
the (campus, data) mesh: the facade splits the engine's campus-sharded
outputs from the shards each device already holds and stacks the carried
per-campus states on their own devices, so neither direction runs a
collective or copies between devices.  Only placement changes: every value
equals the campus's row of the campus-sharded arrays.

Four forced CPU devices need a fresh process (this one has initialized a
1-CPU backend), so one subprocess runs every case and the tests read its
report.  The comparisons stay inside the sharded path; its parity with the
sequential oracle is ``tests/test_grid_region.py``'s.
"""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.grid

_SCRIPT = r"""
import glob, json, os, sys, tempfile
sys.path[:0] = [{root!r}, {src!r}]
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import compliance, fleet, grid, pdu
from repro.power import scenario as SC
from repro.serve import conditioner as SRV
from repro.sharding import rules

assert len(jax.devices()) == 4
hz = 20.0
reg = grid.checkpoint_region(4, 4, duration_s=60.0, sample_hz=hz)
cfg = pdu.make_pdu(sample_dt=1.0 / hz, track_health=True)
spec = compliance.GridSpec.create()
mesh = rules.region_mesh(4)
home = list(rules.campus_rows(mesh)[:, 0])
k = int(round(float(cfg.controller.dt) * hz))
win = 2 * k  # a call: one two-interval chunk, as the benchmark's calls are one chunk
out = {{"mismatch": [], "misplaced": []}}

def equal(name, a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb) or not all(
            np.asarray(x).dtype == np.asarray(y).dtype
            and np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(la, lb)):
        out["mismatch"].append(name)

def placed(name, tree, dev):
    if any(x.devices() != {{dev}} for x in jax.tree_util.tree_leaves(tree)):
        out["misplaced"].append(name)

# Host copies of the engine's campus-sharded (state, observers, aggregates),
# taken as the facade splits them.
seen = []
split = grid._split_campuses
def spy(tree, devices):
    seen.append(jax.tree_util.tree_map(np.asarray, tree))
    return split(tree, devices)
grid._split_campuses = spy

def call(start, state, stop=None):
    r = fleet.condition(reg, cfg, spec, mesh=mesh, stream=dict(
        chunk_intervals=2, state=state, start_sample=start,
        stop_sample=start + win if stop is None else stop))
    jax.block_until_ready(r)
    return r

def check_call(tag, r, t_total, n_ctrl):
    st_h, obs_h, camp_h = seen[-1]
    bank = fleet._make_bank(spec, cfg, t_total)
    campus_sharded = NamedSharding(mesh, P("campus"))
    for f in ("campus_rack", "campus_grid", "soc_mean", "ess_online_frac",
              "health_trace", "safemode_trace"):
        if not getattr(r, f).sharding.is_equivalent_to(
                campus_sharded, getattr(r, f).ndim):
            out["misplaced"].append(f"{{tag}}.{{f}}")
    for c, p in enumerate(r.per_campus):
        for f in ("campus_rack", "campus_grid", "soc_mean", "ess_online_frac",
                  "health_trace", "safemode_trace"):
            equal(f"{{tag}}.{{c}}.{{f}}", getattr(p, f), getattr(r, f)[c])
        row = lambda t: jax.tree_util.tree_map(lambda x: jnp.asarray(x[c]), t)
        want = fleet._finish_streaming(
            cfg, spec, row(st_h), jnp.asarray(camp_h.campus_rack[c, :t_total]),
            jnp.asarray(camp_h.campus_grid[c, :t_total]),
            jnp.asarray(camp_h.soc_mean[c, :n_ctrl]),
            jnp.asarray(camp_h.max_qp_residual[c]), bank, row(obs_h),
            jnp.asarray(camp_h.health[c]),
            jnp.asarray(camp_h.ess_online_frac[c, :n_ctrl]),
            jnp.asarray(camp_h.safemode[c]))
        for f in ("report_rack", "report_grid", "health", "state",
                  "max_qp_residual", "observers"):
            equal(f"{{tag}}.{{c}}.{{f}}", getattr(p, f), getattr(want, f))
        equal(f"{{tag}}.{{c}}.region_state", r.state[c], p.state)
        placed(f"{{tag}}.{{c}}.state", p.state, home[c])
        placed(f"{{tag}}.{{c}}.campus_rack", p.campus_rack, home[c])

state0 = tuple(pdu.init_state(cfg, SC.render(s, 0, 1)[0]) for s in reg.campuses)
out["state0_committed"] = any(
    x.committed for x in jax.tree_util.tree_leaves(state0))
r1 = call(0, state0)
check_call("call1", r1, win, 2)
r2 = call(win, r1.state)  # the carried states, each on its own device
check_call("call2", r2, win, 2)
r2b = call(win, state0)   # the uncommitted first state again
check_call("call2_state0", r2b, win, 2)
try:
    [np.asarray(x) for x in jax.tree_util.tree_leaves(state0)]
    [np.asarray(x) for x in jax.tree_util.tree_leaves(r1.state)]
    out["inputs_alive"] = True
except RuntimeError as e:
    out["inputs_alive"] = str(e)
# The carried stack changes nothing: two calls equal one call over both.
full = call(0, state0, stop=2 * win)
for f in ("campus_rack", "campus_grid", "poi_rack", "poi_grid"):
    equal("resume." + f, np.concatenate(
        [np.asarray(getattr(r1, f)), np.asarray(getattr(r2, f))], axis=-1),
        getattr(full, f))
equal("resume.state", r2.state, full.state)

# The scenarios stack once per region and mesh, campus c's on campus c's
# device; another region object gets its own stack.
import dataclasses
scen_s = grid._stack_campuses(reg, mesh)
out["scenario_reused"] = scen_s is grid._stack_campuses(reg, mesh)
out["scenario_fresh_for_other_region"] = (
    grid._stack_campuses(dataclasses.replace(reg), mesh) is not scen_s)
out["scenario_placed"] = all(
    x.sharding.is_equivalent_to(NamedSharding(mesh, P("campus")), x.ndim)
    and x.shape[0] == 4
    for x in jax.tree_util.tree_leaves(scen_s))
equal("scenario_rows", [
    jax.tree_util.tree_map(lambda x: np.asarray(x)[c], scen_s)
    for c in range(4)], list(reg.campuses))

# One warm call traced: the collectives it runs, by device.
from jax.profiler import ProfileData
from chipbench import spec as bspec, trace as T
from chipbench.run import TraceContext
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
with jax.profiler.TraceAnnotation("bench.window"):
    call(win, r1.state)
jax.profiler.stop_trace()
f = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True),
           key=os.path.getmtime)[-1]
ops, modules = {{}}, set()
for plane in ProfileData.from_file(f).planes:
    if not plane.name.startswith("/host:"):
        continue
    for line in plane.lines:
        for e in line.events:
            st = dict(e.stats)
            if "hlo_op" not in st or "device_ordinal" not in st:
                continue
            name = T.op_name(e.name)
            ops.setdefault(int(st["device_ordinal"]), []).append(
                (name, int(e.start_ns), int(e.end_ns)))
            if name.startswith(("all-", "collective-", "reduce-scatter")):
                modules.add(st.get("hlo_module"))
tr = T.load(d)
tr.devices = {{i: T.Device(ops=v, modules=[]) for i, v in ops.items()}}
ctx = TraceContext(trace=tr, calls=1, devices=sorted(ops), k=k,
                   racks_per_chip=4, intervals_per_call=2, wear=True,
                   horizon=12, qp_iters=30,
                   peaks={{"flops": 1.0, "bytes_per_s": 1.0}})
out["traced_devices"] = sorted(ops)
out["collectives_per_call"] = bspec.reader("collectives_per_call").read(ctx)
out["collective_modules"] = sorted(modules)

# The operator service over the region: a fault on a rack of campus 1, a
# checkpoint, and a resume that equals the live run.
svc = SRV.ConditionerService(cfg, reg, spec, chunk_intervals=2, mesh=mesh)
svc.advance()
svc.inject_fault([5])  # campus 1 (racks 4-7), local rack 1
out["fault_held"] = float(np.asarray(svc.state[1].ess_online)[1]) == 0.0
svc.advance()
ck = svc.checkpoint(os.path.join(d, "ck"))
live = svc.advance()
svc2 = SRV.ConditionerService(cfg, reg, spec, chunk_intervals=2, mesh=mesh)
svc2.restore(ck)
resumed = svc2.advance()
for f in ("campus_rack", "campus_grid", "soc_mean", "health_trace",
          "poi_rack", "poi_grid", "poi_freq_dev", "max_qp_residual"):
    equal("service." + f, getattr(live, f), getattr(resumed, f))
for c in range(reg.n_campuses):
    equal(f"service.{{c}}.report_grid", live.per_campus[c].report_grid,
          resumed.per_campus[c].report_grid)
    placed(f"service.{{c}}.state", svc.state[c], home[c])
    placed(f"service_resumed.{{c}}.state", svc2.state[c], home[c])
equal("service.state", svc.state, svc2.state)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def report():
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    script = _SCRIPT.format(root=root, src=os.path.join(root, "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_each_campus_stays_on_its_device_over_carried_calls(report):
    # Per-campus arrays are the rows of the region's arrays and of the
    # engine's outputs, bitwise, and live on the campus's own device.
    assert report["mismatch"] == [], report["mismatch"]
    assert report["misplaced"] == [], report["misplaced"]
    # The first state is the harness's: uncommitted, on device 0.  Neither
    # it nor a carried state is taken by the engine's donation.
    assert report["state0_committed"] is False
    assert report["inputs_alive"] is True, report["inputs_alive"]


def test_region_call_runs_only_the_fold_and_the_qp_max_collectives(report):
    # Read as the benchmark reads it: the POI fold's all-reduce (its two
    # psums combined, one chunk's worth each) and the eager max over the
    # campuses' QP residuals.  Splitting and stacking add none.
    assert report["traced_devices"] == [0, 1, 2, 3]
    assert report["collectives_per_call"] == 2.0, report
    assert report["collective_modules"] == ["jit__reduce_max", "jit_shard_body"]


def test_scenarios_stack_once_per_region_on_their_own_devices(report):
    # The constant scenarios are stacked on a region's first call and
    # reused after; each campus's row is its own scenario, bitwise.
    assert report["scenario_reused"] is True
    assert report["scenario_fresh_for_other_region"] is True
    assert report["scenario_placed"] is True
    assert "scenario_rows" not in report["mismatch"]


@pytest.mark.service
def test_service_resumes_a_four_device_region_bitwise(report):
    assert report["fault_held"] is True
    assert not [m for m in report["mismatch"] if m.startswith("service")]
    assert not [m for m in report["misplaced"] if m.startswith("service")]

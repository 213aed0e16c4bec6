"""CPU rehearsal of the committed four-chip cell ``region.lockstep``.

The configuration file runs as committed, cut only in racks, sample rate
and length, on four virtual CPU devices (one campus each, the POI folded
by ``psum``) in a subprocess, against the region rehearsal's fixture
limits: the builder and the program have to match the reference, and a
fold that keeps each chip's own share of the POI has to fail ``poi``.
The control fails every call against the committed limits, and the
cell's own reader counts the collectives.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from test_chipbench_rehearsal import _REGION_LIMITS, SEED

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
CELL = "region.lockstep"
# 8 racks a campus, k = 100 samples an interval, two 80 s calls a lap.
SMALL = {"racks": 8, "sample_hz": 20.0, "duration_s": 160.0}
# The fixture tracks no wear; the committed configuration does.
LIMITS = {**_REGION_LIMITS, "wear": 0.15}


def _checkout(tmp_path):
    """The benchmark as committed, with the fixture limits for the cell."""
    base = tmp_path / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    (base / "limits" / f"{CELL}.json").write_text(json.dumps(
        {"limits": {k: {"limit": v} for k, v in LIMITS.items()}}))
    return base


_SCRIPT = r"""
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from chipbench import run
if sys.argv[1] == "no_exchange":
    # Each chip keeps its own campus's share of the POI.
    jax.lax.psum = lambda x, axis_name, **kw: x
run.main(["--workload", {cell!r}, "--seed", "{seed}", "--seconds", "2", "--trace", "0"],
         device_check=False, overrides={small!r})
"""


def test_the_committed_configuration_is_the_region_cell():
    from chipbench import spec

    cell = spec.resolve(ROOT, CELL)
    c = cell.config
    assert cell.chips == 4 == c["campuses"]
    assert (c["racks"], c["sample_hz"], c["duration_s"], c["reduced"]) == (1024, 200.0,
                                                                           3600.0, [])
    campus = spec.resolve(ROOT, "campus.stream").config
    name = c["mix"]["workload"]
    assert c["workloads"] == {name: campus["workloads"][name]}
    assert c["pdu"] == campus["pdu"] and c["pdu"]["track_health"]
    assert set(c["assumed"]) >= {"poi", "bands", "workloads"}


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_the_committed_region_runs_whole_on_four_devices(tmp_path, fault):
    base = _checkout(tmp_path)
    script = _SCRIPT.format(root=str(base.parent), src=os.path.join(ROOT, "src"),
                            cell=CELL, seed=SEED, small=SMALL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script, fault], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert "0 compilations inside the window" in p.stderr
    assert set(line["checks"]) == set(LIMITS)
    if fault == "none":
        assert line["attempted"] > 2  # 2 calls per lap: the window wraps
        assert line["correct"] is True, line["checks"]
        assert set(line["metrics"]) == {"rack_s_per_s", "setup_s"}
    else:
        assert line["correct"] is False
        assert line["checks"]["poi"]["value"] > line["checks"]["poi"]["limit"]


def test_the_control_fails_every_call_of_the_region():
    """The reference one precision down in the program's place fails the
    committed limits in every call."""
    from chipbench import control, spec

    limits = spec.resolve(ROOT, CELL).limits
    assert set(limits) == set(LIMITS)
    got = control.readings(CELL, SEED, overrides=SMALL)
    assert got["correct"] is False and got["failed"] == got["attempted"] > 0
    assert any(v > limits[n] for n, v in got["control"].items()), (got, limits)


def test_collectives_per_call_counts_each_collective_once():
    """A synchronous all-reduce counts once, an asynchronous permute's
    start and done once together; ops outside the window and other ops
    not at all."""
    from chipbench import spec, trace as T
    from chipbench.run import TraceContext

    ops = [("while", 0, 500), ("all-reduce", 100, 110), ("fusion", 120, 130),
           ("collective-permute-start", 200, 201), ("collective-permute-done", 201, 260),
           ("all-reduce", 300, 305), ("all-reduce", 1200, 1300)]
    tr = T.Trace(devices={0: T.Device(ops=ops, modules=[]),
                          1: T.Device(ops=ops[:2], modules=[])},
                 spans=[], window=(0, 1000))
    ctx = TraceContext(trace=tr, calls=2, devices=[0, 1], k=1000, racks_per_chip=1024,
                       intervals_per_call=16, wear=True, horizon=12, qp_iters=30,
                       peaks={"flops": 1.0, "bytes_per_s": 1.0})
    per_call = spec.reader("collectives_per_call").read
    assert per_call(ctx) == pytest.approx((3 / 2 + 1 / 2) / 2)
    quiet = T.Device(ops=[("fusion", 0, 100)], modules=[])
    assert per_call(TraceContext(**{**ctx.__dict__, "trace": T.Trace(
        devices={0: quiet}, spans=[], window=(0, 1000)), "devices": [0]})) is None

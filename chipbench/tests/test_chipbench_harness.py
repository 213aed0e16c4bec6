"""CPU tests of the benchmark's harness: cells found from files by name,
the end-to-end metrics over all work and time, the trace reduction, the
roofline counts, and the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import roofline, spec, stream, trace as T  # noqa: E402

CELLS = ("campus.stream",)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_from_its_files(name):
    cell = spec.resolve(ROOT, name)
    assert cell.chips in (1, 4)
    assert cell.config["builder"]
    assert {"window_intervals", "chunk_intervals", "warmup_calls"} <= set(cell.traffic)
    e2e = [m["name"] for m in cell.end_to_end]
    assert e2e[0] == "rack_s_per_s" and e2e[-1] == "setup_s"
    assert ("call_ms.p95" in e2e) == (name == "campus.stream")
    for m in cell.per_layer:
        assert hasattr(spec.reader(m["name"]), "read")
    for m in cell.end_to_end:
        assert hasattr(spec.end_to_end(m["name"]), "read")
    dep = spec.builder(cell.config["builder"]).build(
        {**cell.config, "racks": 8, "duration_s": 160.0}, 12345)
    w, laps = stream.geometry(dep, cell.traffic)
    assert laps * w == dep.total_samples


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell, configuration, traffic mix and metric added as files and
    entries alone resolve without touching any existing file."""
    base = tmp_path / "chipbench"
    shutil.copytree(os.path.join(ROOT, "chipbench"), base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = json.load(open(os.path.join(ROOT, "chipbench/configs/campus4000_mixed.json")))
    config["name"] = "campus2048_mixed"
    config["racks"] = 2048
    (base / "configs" / "campus2048_mixed.json").write_text(json.dumps(config))
    (base / "traffic" / "stream20s.json").write_text(json.dumps(
        {"window_intervals": 4, "chunk_intervals": 4, "warmup_calls": 2, "trace_seconds": 2}))
    (base / "metrics" / "busy_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    bench["configs"].append({"name": "campus2048_mixed", "source": "x",
                             "file": "chipbench/configs/campus2048_mixed.json",
                             "reduced": ["racks"], "why": "x"})
    bench["workloads"].append({"name": "campus2048.stream", "config": "campus2048_mixed",
                               "traffic": "stream20s", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "busy_ms", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "device",
                               "moves": "rack_s_per_s", "workloads": ["campus2048.stream"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve(str(tmp_path), "campus2048.stream", base=str(base))
    assert cell.config["racks"] == 2048 and cell.traffic["window_intervals"] == 4
    names = [m["name"] for m in cell.per_layer]
    assert names == ["busy_ms"]  # the others list the cells they read
    assert spec.reader("busy_ms", base=str(base)).read(None) == 1.5


class _Run:
    def __init__(self, latencies, wall, racks=1024, call_seconds=40.0):
        self.window = stream.Window(positions=[0] * len(latencies), latencies=latencies,
                                    results=[], wall=wall)
        self.dep = type("D", (), {"n_racks": racks})()
        self.call_seconds = call_seconds
        self.setup_s = 12.5


def test_rate_counts_all_work_over_all_window_time():
    lat = [0.05] * 19 + [0.5]  # one stalled call
    run = _Run(lat, wall=sum(lat) + 0.02)
    rate = spec.end_to_end("rack_s_per_s").read(run)
    assert rate == pytest.approx(1024 * 40.0 * 20 / (sum(lat) + 0.02))
    assert spec.end_to_end("setup_s").read(run) == 12.5


def test_tail_is_over_all_calls_with_the_stall():
    lat = [0.05] * 37 + [2.0] * 3  # three stalled calls of forty
    p95 = spec.end_to_end("call_ms.p95").read(_Run(lat, wall=sum(lat)))
    assert p95 == pytest.approx(np.percentile(np.asarray(lat) * 1e3, 95))
    assert p95 > 50.0  # the stall moves the tail
    assert spec.end_to_end("call_ms.p95").read(_Run([0.05] * 40, wall=2.0)) == pytest.approx(50.0)


def _ctx(devices, calls=2, **kw):
    from chipbench.run import TraceContext

    tr = T.Trace(devices=devices, spans=kw.pop("spans", []), window=(0, 1000))
    base = dict(trace=tr, calls=calls, devices=sorted(devices), k=1000, racks_per_chip=1024,
                intervals_per_call=8, wear=True, horizon=12, qp_iters=30,
                peaks={"flops": 197e12, "bytes_per_s": 819e9})
    base.update(kw)
    return TraceContext(**base)


def _device():
    # A loop (0-600) holding a megakernel, an ADMM call and a fusion; an
    # all-reduce after it; a gap 700-900; two module launches and a third
    # outside the window.
    ops = [("while", 0, 600), ("pdu_health_sim", 10, 310), ("admm_iterate", 320, 340),
           ("add_fusion", 350, 400), ("all-reduce", 600, 700), ("copy", 900, 950)]
    modules = [("jit_run", 0, 700), ("jit_copy", 900, 950), ("jit_copy", 1200, 1300)]
    return T.Device(ops=ops, modules=modules)


def test_trace_reduction_on_a_small_trace():
    dev = _device()
    win = (0, 1000)
    assert T.busy_ns(dev, win) == 750
    assert T.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert T.gaps([(0, 10), (20, 30)], 0, 40) == [(10, 20), (30, 40)]
    own = T.self_times(T.clip(dev.ops, win))
    assert own["while"] == 600 - 300 - 20 - 50
    assert own["pdu_health_sim"] == 300
    ctx = _ctx({0: dev})
    assert spec.reader("idle_share").read(ctx) == pytest.approx(25.0)
    assert spec.reader("megakernel_ms").read(ctx) == pytest.approx(300 / 2 / 1e6)
    assert spec.reader("admm_ms").read(ctx) == pytest.approx(20 / 2 / 1e6)
    assert spec.reader("xla_ms").read(ctx) == pytest.approx((750 - 320) / 2 / 1e6)
    assert spec.reader("collective_ms").read(ctx) == pytest.approx(100 / 2 / 1e6)
    assert spec.reader("launches_per_call").read(ctx) == pytest.approx(1.0)
    ops, nbytes = roofline.megakernel(1000, 1024, True)
    least, _ = roofline.least_seconds(ops, nbytes, ctx.peaks)
    assert spec.reader("megakernel_roofline").read(ctx) == pytest.approx(
        100 * least * 8 * 2 / 300e-9)


def test_readers_find_nothing_where_nothing_ran():
    dev = T.Device(ops=[("fusion", 0, 100)], modules=[("jit_run", 0, 100)])
    ctx = _ctx({0: dev})
    for name in ("megakernel_ms", "megakernel_roofline", "admm_ms", "admm_roofline",
                 "collective_ms"):
        assert spec.reader(name).read(ctx) is None
    assert spec.reader("idle_share").read(_ctx({})) is None


def test_idle_gaps_are_named_by_the_open_host_span():
    # Idle: 700-900 (middle 800, inside the call) and 950-1000 (waiting).
    spans = [("bench.window", 0, 1000), ("bench.call", 0, 820), ("bench.wait", 820, 1000)]
    tr = T.Trace(devices={0: _device()}, spans=spans, window=(0, 1000))
    assert T.idle_by_span(tr.devices[0], tr) == {"bench.call": 200, "bench.wait": 50}


def test_a_recorded_trace_loads_with_its_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) + 1.0)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.call"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    names = {n for n, _, _ in tr.spans}
    assert {"bench.window", "bench.call"} <= names
    assert tr.window[1] > tr.window[0]


def test_op_names_as_the_trace_writes_them():
    assert T.op_name("%pdu_health_sim.8 = (f32[1000,1024]) custom-call(...)") == "pdu_health_sim"
    assert T.op_name("%all-reduce.3 = f32[1000] all-reduce(...)") == "all-reduce"
    assert T.op_name("%while.264 = (s32[]) while(...)") == "while"
    assert T.op_name("%add_subtract_fusion.5 = (f32[48]) fusion(...)") == "add_subtract_fusion"


def test_roofline_counts_match_the_repository_table():
    # benchmarks/make_roofline_table.py, at k = 1000 samples, R = 1024
    # racks: 73 operations per rack-sample for the megakernel; for ADMM
    # its per-iteration formula 2n(n+m) + 2(m-2h)n + 6m + 2n, evaluated
    # here with n = 2h, the length of the iterate x.
    ops, nbytes = roofline.megakernel(1000, 1024, wear=True)
    assert ops == 73 * 1000 * 1024
    assert nbytes == 4 * (1000 * 1024 + (13 + 11 + 4) * 1024 + 1000)
    assert roofline.megakernel(1000, 1024, wear=False)[0] == 48 * 1000 * 1024
    h = 12
    n, m = 2 * h, 3 * h
    assert roofline.admm_ops_per_iter(h) == 2 * n * (n + m) + 2 * (m - 2 * h) * n + 6 * m + 2 * n
    ops, nbytes = roofline.admm(h, 30, 1024)
    assert ops == 3720 * 30 * 1024
    pk = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_seconds(*roofline.megakernel(1000, 1024, True), pk)
    assert bound == "memory" and 4e-6 < t < 6e-6
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_the_traffic_follows_the_operator_loop():
    """A call conditions what one ``ConditionerService.advance`` does by
    default: one chunk of the service's default chunk length."""
    import inspect

    from repro.serve.conditioner import ConditionerService

    default = inspect.signature(ConditionerService).parameters["chunk_intervals"].default
    traffic = spec.resolve(ROOT, "campus.stream").traffic
    assert traffic["window_intervals"] == traffic["chunk_intervals"] == default


def test_the_run_refuses_without_a_tpu():
    from chipbench import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "campus.stream", "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload",
         "campus.stream", "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

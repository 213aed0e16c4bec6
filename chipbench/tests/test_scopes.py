"""CPU tests of the reduction by the program's spans and scopes
(``chipbench/scopes.py``): scoped self times on a small trace, idle time
named by the innermost host span, and the facade's spans as a recorded
call writes them."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import scopes as S  # noqa: E402

BODY = "jit(run)/while/body/closed_call"


def _ops():
    # The engine's chunk loop (0-600) holds a rendered block, the
    # observers' own per-sample loop, the controller's QP preparation
    # around the ADMM kernel, and the megakernel; a facade program after
    # it (700-720); the device idles 600-700 and 720-1000.
    return [
        ("while", 0, 600, "jit(run)/while"),
        ("select_select_fusion", 10, 60, f"{BODY}/repro.render/select_n"),
        ("while", 100, 400, f"{BODY}/repro.observers/while"),
        ("add_subtract_fusion", 110, 300, f"{BODY}/repro.observers/while/body/sub"),
        ("dynamic_slice", 300, 350, f"{BODY}/repro.observers/while/body/dynamic_slice"),
        ("admm_iterate", 420, 450, f"{BODY}/repro.controller/jit(admm_iterate)/pallas_call"),
        ("fusion", 450, 480, f"{BODY}/repro.controller/mul"),
        ("pdu_health_sim", 480, 590, f"{BODY}/jit(pdu_health_sim)/pallas_call"),
        ("copy", 700, 720, "jit(copy)/copy"),
    ]


SPANS = [("bench.window", 0, 1000), ("bench.call", 0, 800), ("repro.condition", 5, 790),
         ("repro.prepare", 5, 8), ("repro.engine", 8, 12), ("repro.finish", 590, 785),
         ("bench.wait", 800, 1000)]


def test_scopes_read_self_times_inside_scoped_loops():
    ops, win = _ops(), (0, 1000)
    assert S.scope_ns(ops, win, "render") == 50
    # The observers' loop counts its own overhead (300 - 190 - 50) and its body.
    assert S.scope_ns(ops, win, "observers") == 60 + 190 + 50
    assert S.scope_ns(ops, win, "controller") == 60
    assert S.scope_ns(ops, win, "controller", exclude=("admm_iterate",)) == 30
    assert S.scope_ns(ops, win, "health") is None
    # A scope is a whole path segment, not a prefix of one.
    assert S.scope_ns([("f", 0, 5, "a/repro.rendering/b")], win, "render") is None


def test_readings_per_call_stay_inside_the_rest_of_busy_time():
    tr = S.Scoped(ops={0: _ops()}, spans=SPANS, window=(0, 1000))
    r = S.per_call(tr, [0], calls=2)
    assert r["render_ms"] == pytest.approx(50 / 2 / 1e6)
    assert r["observers_ms"] == pytest.approx(300 / 2 / 1e6)
    assert r["qp_prep_ms"] == pytest.approx(30 / 2 / 1e6)
    assert r["busy_ms"] == pytest.approx(620 / 2 / 1e6)
    assert r["kernels_ms"] == pytest.approx(140 / 2 / 1e6)
    scoped = r["render_ms"] + r["observers_ms"] + r["qp_prep_ms"]
    assert scoped <= r["busy_ms"] - r["kernels_ms"]
    assert r["facade_idle_ms"] == pytest.approx(100 / 2 / 1e6)


def test_facade_idle_counts_only_gaps_inside_the_facade():
    ops, win = _ops(), (0, 1000)
    # 600-700 (middle 650, inside repro.condition) counts; 720-1000 (middle
    # 860, in bench.wait) does not.
    assert S.idle_under(ops, win, SPANS, "repro.condition") == 100
    assert S.idle_under(ops, win, SPANS[:3], "repro.finish") is None
    no_spans = S.Scoped(ops={0: ops}, spans=[("bench.window", 0, 1000)], window=win)
    assert "facade_idle_ms" not in S.per_call(no_spans, [0], calls=2)


def test_idle_goes_to_the_innermost_span_program_or_benchmark():
    ops, win = _ops(), (0, 1000)
    assert S.idle_by_span(ops, win, SPANS) == {"repro.finish": 100, "bench.wait": 280}
    # Without the program's spans the same gap is the benchmark's call.
    bench_only = [s for s in SPANS if s[0].startswith("bench.")]
    assert S.idle_by_span(ops, win, bench_only) == {"bench.call": 100, "bench.wait": 280}


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field number, int | str | bytes) pairs;
    a float is written as a fixed64 double."""
    import struct

    out = b""
    for num, v in fields:
        if isinstance(v, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", v)
        elif isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_scopes_read_the_event_metadata_of_device_planes():
    render, obs = f"{BODY}/repro.render/add", f"{BODY}/repro.observers/mul"
    stat = lambda i, name: (5, _msg((1, i), (2, _msg((1, i), (2, name)))))
    event = lambda i, text, *stats: (4, _msg((1, i), (2, _msg((1, i), (2, text), *stats))))
    device = _msg(
        (1, 7), (2, "/device:TPU:0"), stat(1, "flops"), stat(2, "tf_op"), stat(3, obs),
        event(10, "%fusion.3 = f32[8] fusion(%p)", (5, _msg((1, 1), (2, 4.0))),
              (5, _msg((1, 2), (5, render)))),
        event(11, "%mul.2 = f32[8] multiply(%a, %b)", (5, _msg((1, 2), (7, 3)))),
        event(12, "%copy.1 = f32[8] copy(%p)"),
        event(13, "%add.1 = f32[] add(%a, %b)", (5, _msg((1, 2), (5, render)))),
        event(14, "%add.1 = f32[] add(%a, %b)", (5, _msg((1, 2), (5, "jit(copy)/add")))))
    host = _msg((2, "/host:CPU"), stat(2, "tf_op"),
                event(10, "%host.1", (5, _msg((1, 2), (5, render)))))
    scopes = S.op_scopes(_msg((1, device), (1, host)))
    assert scopes["%fusion.3 = f32[8] fusion(%p)"] == render
    assert scopes["%mul.2 = f32[8] multiply(%a, %b)"] == obs  # an interned string
    assert scopes["%copy.1 = f32[8] copy(%p)"] == ""
    assert scopes["%add.1 = f32[] add(%a, %b)"] == ""  # two ops, two paths
    assert "%host.1" not in scopes


def test_a_recorded_call_writes_the_facade_spans_nested(tmp_path):
    import jax

    from chipbench import program, spec

    cell = spec.resolve(ROOT, "campus.stream")
    config = {**cell.config, "racks": 8, "duration_s": 160.0, "sample_hz": 20.0}
    dep = spec.builder(config["builder"]).build(config, 3000000019)
    system = program.System(dep, jax.devices()[:1])
    w = int(cell.traffic["window_intervals"]) * dep.k
    program.block(system.call(system.state0, 0, w, int(cell.traffic["chunk_intervals"])))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        program.block(system.call(system.state0, 0, w, int(cell.traffic["chunk_intervals"])))
    jax.profiler.stop_trace()
    tr = S.load(str(tmp_path))
    by = {}
    for n, s, e in tr.spans:
        by.setdefault(n, []).append((s, e))
    phases = ("repro.prepare", "repro.engine", "repro.finish")
    assert all(len(by.get(n, ())) == 1 for n in ("repro.condition",) + phases), by
    (c0, c1), = by["repro.condition"]
    (p, _), (e, _), (f, _) = (by[n][0] for n in phases)
    assert all(c0 <= by[n][0][0] and by[n][0][1] <= c1 for n in phases)
    assert p < e < f


def test_the_command_refuses_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        S.main(["--workload", "campus.stream", "--seed", "3000000019"])
    assert e.value.code not in (0, None)

"""The control of the comparison: the reference put in the program's
place, computed one precision below the configuration's (per-sample
arithmetic in bfloat16, matrix products at HIGH instead of float32 at
HIGHEST), and compared with the float32 reference exactly as a run
compares the program.  Its numbers are the upper readings the limits are
set below; the benchmark's own runs never run it.

    python3 chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

prints one JSON line per seed with every compared number over a whole
lap of the stream, judged against the cell's committed limits as a run
judges the program (``correct`` has to come out false).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def program_like(dep, ref, call: dict) -> dict:
    """A reference call dressed as the program's outputs."""
    from chipbench import compare

    out = dict(call)
    rep = compare.reference_reports(dep, call, ref.dtype)
    out.update(max_ramp=rep["max_ramp"], spec_worst=rep["spec_worst"],
               health_trace=ref.wear_snapshot(call["state"]))
    if dep.region:
        out.update(poi_grid=rep["poi_grid"], poi_freq_dev=rep["poi_freq_dev"],
                   mode_mags=rep["mode_mags"])
    return out


def readings(workload: str, seed: int, *, overrides=None, calls=None) -> dict:
    """The control's ``compare.judge`` for one seed: ``correct``,
    ``failed`` calls of ``attempted``, and ``control``, {number: worst
    over the compared calls}."""
    import jax
    import jax.numpy as jnp

    from chipbench import compare, spec, stream
    from chipbench.reference import conditioner

    cell = spec.resolve(ROOT, workload)
    config = {**cell.config, **(overrides or {})}
    dep = spec.builder(config["builder"]).build(config, seed)
    w, laps = stream.geometry(dep, cell.traffic)
    n = laps if calls is None else min(calls, laps)
    ref = conditioner.Reference(dep)
    ctl = conditioner.Reference(dep, dtype=jnp.bfloat16, precision=jax.lax.Precision.HIGH)
    r_calls, c_calls = ref.run(n, w), ctl.run(n, w)
    per_call = []
    for rc, cc in zip(r_calls, c_calls):
        per_call.append(compare.numbers(
            dep, program_like(dep, ctl, cc), rc, compare.reference_reports(dep, rc),
            ref.wear_snapshot(rc["state"])))
    correct, failed, worst = compare.judge(per_call, cell.limits)
    return {"correct": correct, "failed": failed, "attempted": len(per_call),
            "control": worst}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    import jax

    print(json.dumps({"device": jax.devices()[0].device_kind, "count": len(jax.devices())}),
          flush=True)
    for seed in args.seeds:
        print(json.dumps({"seed": seed, **readings(args.workload, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

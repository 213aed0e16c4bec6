"""Benchmark harness: one function per paper table/figure + kernel benches.

Prints ``name,us_per_call,derived`` CSV (and a roofline summary if dry-run
records exist under experiments/dryrun/), and writes a machine-readable
``BENCH_power.json`` (``{bench_name: us_per_call}``) at the repo root so
the perf trajectory is tracked across PRs.

``--gate [PCT]`` turns the run into a CI perf check: fresh timings are
compared against the committed ``BENCH_power.json`` and the process exits
non-zero if any tracked bench regressed by more than PCT percent (default
25).  Quick runs (``--quick``) compare against the ``quick:``-prefixed
baseline entries (quick workloads are smaller, so their timings live in a
separate namespace); seed them once with ``--quick --update-baseline``.
``python benchmarks/run.py --quick --gate`` is then a one-command CI smoke:
correctness asserts (engine agreement) + perf regression gate.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def gate_records(
    records: dict[str, float],
    baseline: dict[str, float],
    pct: float,
    quick: bool,
) -> list[str]:
    """Regression check: every fresh timing vs its committed baseline entry.

    Returns human-readable failure lines (empty = gate passes).  Benches
    without a baseline entry are skipped — a new bench cannot fail the
    gate before its baseline is recorded.
    """
    failures = []
    for name, us in records.items():
        prev = baseline.get(f"quick:{name}" if quick else name)
        if not prev:
            continue
        reg = (us / prev - 1.0) * 100.0
        if reg > pct:
            failures.append(
                f"{name}: {prev:.0f}us -> {us:.0f}us (+{reg:.0f}% > {pct:.0f}%)"
            )
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--quick",
        action="store_true",
        help="CI smoke mode: shrink fleet sizes / trace durations and skip "
        "writing BENCH_power.json (timings are not comparable to full runs)",
    )
    ap.add_argument(
        "--gate",
        nargs="?",
        const=25.0,
        type=float,
        default=None,
        metavar="PCT",
        help="exit non-zero if any tracked bench regressed >PCT%% vs the "
        "committed BENCH_power.json (default 25); implies no baseline "
        "rewrite unless --update-baseline",
    )
    ap.add_argument(
        "--update-baseline",
        action="store_true",
        help="write this run's timings into BENCH_power.json (quick runs "
        "record under 'quick:'-prefixed keys; the default full run writes "
        "anyway unless --gate is set)",
    )
    args = ap.parse_args()
    # A pre-set env var also selects quick sizes (they bind when the bench
    # modules import), so treat it exactly like --quick — otherwise quick
    # timings would silently overwrite the tracked BENCH_power.json.
    quick = args.quick or os.environ.get("REPRO_BENCH_QUICK", "") == "1"
    if quick:
        # must be set before the bench modules import (sizes bind at import)
        os.environ["REPRO_BENCH_QUICK"] = "1"

    # Make both ``repro`` and the ``benchmarks`` package importable when run
    # as a plain script (``python benchmarks/run.py``) from anywhere.
    sys.path.insert(0, _REPO_ROOT)
    sys.path.insert(0, os.path.join(_REPO_ROOT, "src"))
    from repro.utils import compile_cache

    compile_cache.configure()
    from benchmarks import kernel_benches, paper_benches

    # The tracked trajectory from the previous PR: read it BEFORE the run so
    # the per-bench delta is printed even when this run overwrites the file.
    bench_path = os.path.join(_REPO_ROOT, "BENCH_power.json")
    baseline: dict[str, float] = {}
    if os.path.exists(bench_path):
        with open(bench_path) as f:
            baseline = json.load(f)

    print("name,us_per_call,derived")
    failures = 0
    records: dict[str, float] = {}
    for fn in paper_benches.ALL + kernel_benches.ALL:
        try:
            name, us, derived = fn()
            records[name] = round(float(us), 1)
            print(f"{name},{us:.0f},{derived}")
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{fn.__name__},ERROR,{type(e).__name__}: {e}")
        sys.stdout.flush()

    # Per-bench delta vs the previous BENCH_power.json + derived metrics
    # (us/rack, samples/s) for the benches that registered their workload
    # size in paper_benches.UNITS.  Quick runs shrink the workloads, so
    # their timings are not comparable to the tracked baseline — skip.
    if not quick:
        header = "prev_us,now_us,speedup,us_per_rack,samples_per_s"
        print(f"\n# perf trajectory vs previous BENCH_power.json\n# name,{header}")
        for name, us in records.items():
            prev = baseline.get(name)
            prev_s = f"{prev:.0f}" if prev else "-"
            speedup = f"{prev / us:.2f}x" if prev else "-"
            units = paper_benches.UNITS.get(name, {})
            upr = f"{us / units['racks']:.0f}" if units.get("racks") else "-"
            sps = f"{units['samples'] / (us / 1e6):.2e}" if units.get("samples") else "-"
            print(f"# {name},{prev_s},{us:.0f},{speedup},{upr},{sps}")

    # Baseline writes.  A gated run never rewrites its own reference unless
    # explicitly asked; quick entries live under "quick:" so full-run
    # numbers and CI-smoke numbers can coexist in one file.
    write = (not quick and args.gate is None) or args.update_baseline
    if write:
        if quick:
            merged = dict(baseline)
            merged.update({f"quick:{k}": v for k, v in records.items()})
        else:
            merged = {k: v for k, v in baseline.items() if k.startswith("quick:")}
            merged.update(records)
        with open(bench_path, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {bench_path} ({len(records)} benches)")
    elif quick:
        print(f"# --quick smoke run: BENCH_power.json not written ({len(records)} benches ran)")

    # roofline summary from dry-run records, if present
    recs = sorted(glob.glob("experiments/dryrun/*__16_16.json"))
    if recs:
        print("\n# roofline (single-pod dry-run records)")
        print("cell,bottleneck,compute_s,memory_s,collective_s,useful_flop_ratio,fits_16gb")
        for p in recs:
            r = json.load(open(p))
            rl = r["roofline"]
            print(
                f"{r['arch']}/{r['shape']},{rl['bottleneck']},{rl['compute_s']:.4f},"
                f"{rl['memory_s']:.4f},{rl['collective_s']:.4f},"
                f"{r['useful_flop_ratio']:.3f},{r['fits_16gb']}"
            )

    if args.gate is not None:
        # A gated run also fails on bench-internal assertion errors (e.g.
        # the safe-mode supervision-overhead budget), not just timing
        # regressions vs the baseline.
        if failures:
            print(f"\n# PERF GATE FAILED ({failures} bench(es) errored)")
            sys.exit(1)
        gate_failures = gate_records(records, baseline, args.gate, quick)
        if gate_failures:
            print(f"\n# PERF GATE FAILED (>{args.gate:.0f}% regression):")
            for line in gate_failures:
                print(f"#   {line}")
            sys.exit(1)
        compared = sum(
            1 for n in records if baseline.get(f"quick:{n}" if quick else n)
        )
        print(f"\n# perf gate OK ({compared}/{len(records)} benches vs baseline, "
              f"threshold {args.gate:.0f}%)")

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

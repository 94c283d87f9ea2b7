"""Aggregate benchmark runner — one section per paper table/figure plus
the kernel micro-benchmarks.  Prints ``name,value,paper_reference,derived``
CSV rows (see common.emit).

    PYTHONPATH=src python -m benchmarks.run [--skip capacity,...]

``--perf-json`` additionally writes the machine-readable perf-trajectory
file (BENCH_perf.json): wall seconds and ticks/sec for the requested
Table 2 capacity cases on the jnp path plus a scaled-down
pallas-interpret case, so the hot-path trend is tracked across PRs, and
bytes/tick per mode (default / +net / +faults) from XLA cost_analysis —
the timing-noise-free footprint metric behind the mode-keyed pool layout
(DESIGN.md §2.2).

    PYTHONPATH=src python -m benchmarks.run --only perf \
        --perf-json BENCH_perf.json --perf-cases case1b,case2b
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def write_perf_json(path: str, cases, repeats: int = 2) -> None:
    """Best-of-N wall times per case (the capacity numbers are wall-clock
    on a shared machine; best-of is the stable statistic).  The
    ``seed_baseline_wall_s`` block of an existing file is carried over and
    speedups recomputed, so regeneration preserves the cross-PR trend."""
    import os

    import jax

    from . import bench_capacity

    baselines = {}
    bytes_baseline = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            baselines = prev.get("seed_baseline_wall_s", {})
            bytes_baseline = prev.get("pr3_bytes_per_tick", {})
        except (OSError, ValueError):
            pass

    records = []
    for tag in cases:
        best = None
        for _ in range(max(repeats, 1)):
            rec = bench_capacity.perf_record(tag, backend="jnp")
            if best is None or rec["wall_s"] < best["wall_s"]:
                best = rec
        records.append(best)
        print(f"# perf {tag}: {best['wall_s']:.2f}s "
              f"({best['ticks_per_s']:.0f} ticks/s, best of {repeats})")
    # Per-phase overhead ratios on case1b — each variant re-runs the same
    # case with one more phase compiled in, and the wall ratio over the
    # phase-off run prices that phase's per-tick cost:
    #   +net    Transit (amply-provisioned NICs)           target ≤ 1.3×
    #   +faults Disruption, mild chaos (DESIGN.md §7)      target ≤ 1.3×
    #   +chaos2 FULL §7.1 gray surface on top              target ≤ 1.3×
    #   +obs    streaming telemetry (§9)                   target ≤ 1.05×
    #   +slo    burn-rate Alerting on top, objectives ON   target ≤ 1.1×
    #           (its [C,2] SLI scatter-add is real per-tick pool work
    #           the pure-observation budget doesn't cover)
    # Baseline and variant repeats are INTERLEAVED (base, variant, base,
    # variant, …) and each side takes its own best: the container's wall
    # clock drifts several percent over the minutes a sequential protocol
    # spans (case2b churns 50M cloudlets between the one-off baseline and
    # the variants), which used to swamp the small ratios.  The
    # interleaved baseline rides along as ``base_wall_s``.
    variants = [
        ("net", dict(network=True), "net_overhead_ratio", "network-off"),
        ("faults", dict(faults=True), "faults_overhead_ratio",
         "fault-free"),
        ("chaos2", dict(chaos2=True), "chaos2_overhead_ratio",
         "fault-free"),
        ("obs", dict(telemetry=True), "obs_overhead_ratio",
         "telemetry-off"),
        ("slo", dict(slo=True), "slo_overhead_ratio", "telemetry-off"),
    ]
    if "case1b" in cases:
        for name, kw, ratio_key, vs in variants:
            best, base_wall = None, float("inf")
            for _ in range(max(repeats, 1)):
                base_wall = min(base_wall, bench_capacity.perf_record(
                    "case1b", backend="jnp")["wall_s"])
                rec = bench_capacity.perf_record("case1b", backend="jnp",
                                                 **kw)
                if best is None or rec["wall_s"] < best["wall_s"]:
                    best = rec
            best[ratio_key] = round(best["wall_s"] / max(base_wall, 1e-9),
                                    3)
            best["base_wall_s"] = round(base_wall, 4)
            records.append(best)
            print(f"# perf case1b+{name}: {best['wall_s']:.2f}s "
                  f"({best[ratio_key]}x of {vs}, interleaved base "
                  f"{base_wall:.2f}s)")
    # interpret-mode kernel trend on a scaled-down case (interpret is
    # orders of magnitude slower — the trend matters, not the magnitude)
    rec = bench_capacity.perf_record("case1a", backend="pallas-interpret",
                                     scale=0.01)
    records.append(rec)
    print(f"# perf case1a/pallas-interpret(x0.01): {rec['wall_s']:.2f}s")
    for rec in records:
        base = baselines.get(rec["case"])
        if base and rec["backend"] == "jnp" and rec.get("scale", 1.0) == 1.0:
            rec["speedup_vs_seed"] = round(base / rec["wall_s"], 2)
    # batched-sweep economics (see bench_scaling.sweep8_demo docstring)
    from . import bench_scaling
    ratio, seq_ratio = bench_scaling.sweep8_demo(duration_s=120.0)
    records.append({
        "case": "sweep8_hs", "backend": "jnp",
        "batch_over_solo": round(ratio, 3),
        "batch_over_sequential": round(seq_ratio, 3),
        "cpu_count": os.cpu_count(),
    })
    doc = {
        "generated_unix": int(time.time()),
        "jax_backend": jax.default_backend(),
        "records": records,
    }
    # bytes/tick per mode from XLA cost_analysis — NOT wall clock (container
    # walls drift within a session); this is the stable footprint metric
    # for the mode-keyed pool layout (DESIGN.md §2.2).  The PR-3 baseline
    # (fixed 10-int/5-float layout) is carried over so the reclaim ratio
    # stays comparable across regenerations.
    if "case1b" in cases:
        bpt = {}
        for mode_tag, kw in (("case1b", {}),
                             ("case1b+net", dict(network=True)),
                             ("case1b+faults", dict(faults=True)),
                             ("case1b+chaos2", dict(chaos2=True))):
            bpt[mode_tag] = round(
                bench_capacity.bytes_per_tick("case1b", **kw), 1)
            base = bytes_baseline.get(mode_tag)
            ratio = f" ({bpt[mode_tag] / base - 1.0:+.1%} vs pr3)" \
                if base else ""
            print(f"# bytes/tick {mode_tag}: {bpt[mode_tag]:.0f}{ratio}")
        doc["bytes_per_tick"] = bpt
        if bytes_baseline:
            doc["pr3_bytes_per_tick"] = bytes_baseline
            doc["bytes_reclaim_vs_pr3"] = {
                k: round(1.0 - v / bytes_baseline[k], 4)
                for k, v in bpt.items() if bytes_baseline.get(k)}
    if baselines:
        doc["seed_baseline_wall_s"] = baselines
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"# wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip", default="",
                    help="comma list: capacity,generator,response,scaling,"
                         "kernels,perf")
    ap.add_argument("--only", default="")
    ap.add_argument("--perf-json", default="",
                    help="path for the machine-readable perf records "
                         "(enables the perf section)")
    ap.add_argument("--perf-cases", default="case1b,case2b",
                    help="Table 2 cases to time for --perf-json")
    ap.add_argument("--perf-repeats", type=int, default=2)
    args = ap.parse_args()
    skip = set(filter(None, args.skip.split(",")))
    only = set(filter(None, args.only.split(",")))
    from repro import compile_cache
    compile_cache.enable()

    from . import (bench_capacity, bench_generator, bench_kernels,
                   bench_response, bench_scaling)
    sections = [
        ("generator", bench_generator.main),   # Fig 9
        ("capacity", bench_capacity.main),     # Table 2
        ("response", bench_response.main),     # Fig 10
        ("scaling", bench_scaling.main),       # Fig 11
        ("kernels", bench_kernels.main),
    ]
    if args.perf_json:
        cases = [c for c in args.perf_cases.split(",") if c]
        sections.append(
            ("perf", lambda: write_perf_json(args.perf_json, cases,
                                             args.perf_repeats)))
    failed = []
    for name, fn in sections:
        if name in skip or (only and name not in only):
            print(f"# --- skipping {name} ---")
            continue
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            failed.append(name)
            print(f"# !!! section {name} FAILED")
            traceback.print_exc()
        print(f"# --- {name} done in {time.perf_counter() - t0:.1f}s ---",
              flush=True)
    if failed:
        sys.exit(f"failed sections: {failed}")


if __name__ == "__main__":
    main()

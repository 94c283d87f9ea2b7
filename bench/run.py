#!/usr/bin/env python3
"""CloudNativeSim benchmark: simulated seconds per wall second over a
closed loop of simulation jobs on one accelerator.

    python3 bench/run.py --workload case1b.seeds --seed 7 --seconds 10 \\
        --trace 0

``--trace 0`` sets up (build, compile or cache load, one warm-up job),
then runs jobs back to back for ``--seconds`` and reports the cell's
end-to-end metrics.  ``--trace 1`` sets up the same way, profiles one
whole job and reports the per-layer metrics.  Either way the jobs
compared with the configuration's plain reference (a sample drawn from
the seed) decide ``correct``; the numbers compared are printed beside
their limits as the last lines of standard error and under ``checks`` in
the result, the last line of standard output.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# JAX's persistent compile cache: a fixed directory inside the checkout
# (the path is part of the cache's key).
CACHE_DIR = ROOT / ".jax_cache"
PLATFORM = "tpu"
N_COMPARED_JOBS = 3


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def require_devices(chips: int) -> list:
    """The accelerator devices, or exit non-zero: a measurement never
    falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != PLATFORM or len(devs) < chips:
        print(f"bench: needs {chips} {PLATFORM} device(s), JAX found "
              f"{devs}", file=sys.stderr)
        raise SystemExit(3)
    return devs


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def compared_jobs(jobs: list, seed: int) -> list:
    """The sample of the window's jobs the reference checks."""
    import numpy as np
    rng = np.random.default_rng([int(seed), 0x5EED])
    n = min(N_COMPARED_JOBS, len(jobs))
    return [jobs[i] for i in sorted(rng.choice(len(jobs), n, replace=False))]


def check(cell, jobs: list, seed: int, bench_dir=BENCH_DIR) -> tuple:
    """(correct, checks): the compared numbers with their limits."""
    from cnsbench import spec

    ref = spec.reference_module(cell.config, bench_dir)
    checks = {}
    failed_jobs = sum(1 for j in jobs if j.failures)
    checks["jobs_failing_invariants"] = dict(value=failed_jobs, limit=0)
    if ref is None:
        checks["reference_found"] = dict(value=0, limit=1)
        return False, checks
    t0 = time.perf_counter()
    values = ref.compare(cell.config, cell.traffic, compared_jobs(jobs, seed))
    for name, value in values.items():
        checks[name] = dict(value=value, limit=ref.LIMITS[name])
    print(f"bench: reference over {min(N_COMPARED_JOBS, len(jobs))} jobs "
          f"took {time.perf_counter() - t0:.3f} s", file=sys.stderr)
    ok = failed_jobs == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def traced_job(jobs, sim) -> dict:
    """Profile one whole job; returns the reader context."""
    import jax
    from cnsbench import trace

    log_dir = tempfile.mkdtemp(prefix="cnsbench-trace-")
    try:
        hooks = {k: (lambda k=k: jax.profiler.TraceAnnotation(
            trace.SPAN_PREFIX + k)) for k in ("run", "readback", "check")}
        with jax.profiler.trace(log_dir):
            with jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + "job"):
                job = jobs.run(0, hooks=hooks)
        path = trace.find_xplane(log_dir)
        keep = os.environ.get("CNSBENCH_KEEP_TRACE")
        if keep:
            pathlib.Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, os.path.join(keep, "job.xplane.pb"))
            pathlib.Path(keep, "module.hlo.txt").write_text(
                program_text(sim))
        devices, spans = trace.read_xplane(path)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    text = program_text(sim)
    red = trace.reduce_trace(devices, spans, trace.phase_map(text),
                             trace.module_name(text))
    return dict(job=job, reduction=red, ticks=jobs.ticks,
                n_devices=max(len(devices), 1))


def program_text(sim) -> str:
    """Optimised HLO text of the program the cell's jobs ran.  The engine
    keeps its compiled executables in ``Simulation._compiled_cache``,
    keyed by device and program; the benchmark reads the one compiled
    for this deployment's device (no public accessor exists)."""
    from repro.core import Simulation
    texts = [c.as_text() for key, c in Simulation._compiled_cache.items()
             if PLATFORM in str(key[:2])]
    if len(texts) != 1:
        raise RuntimeError(f"expected one compiled run program for the "
                           f"device, found {len(texts)}")
    return texts[0]


def main(argv=None, root=ROOT, bench_dir=BENCH_DIR, cache=True) -> int:
    """One run.  ``root`` holds BENCHMARK.json and ``bench_dir`` the
    cells' files (tests point them at a small copy, with no compile
    cache)."""
    args = parse_args(argv)
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    from cnsbench import build, jobs as jobsmod, spec, trace

    cell = spec.load_cell(args.workload, root, bench_dir)
    if cache:
        enable_cache()
    marks = [("imports", time.perf_counter())]
    devs = require_devices(cell.chips)
    marks.append(("devices", time.perf_counter()))
    sim = build.build(cell.config, cell.traffic)
    ref = spec.reference_module(cell.config, bench_dir)
    jobs = jobsmod.Jobs(sim, cell.traffic, args.seed,
                        extra=getattr(ref, "READBACK", ()))
    marks.append(("build", time.perf_counter()))
    warm = jobs.run(jobsmod.WARMUP_JOB)
    marks.append(("warm-up job", time.perf_counter()))
    setup_s = marks[-1][1] - T_PROCESS
    parts = ", ".join(f"{name} {t - t0:.3f} s" for (name, t), t0 in zip(
        marks, [T_PROCESS] + [t for _, t in marks]))
    print(f"bench: set-up {setup_s:.3f} s ({parts}; engine scan of the "
          f"warm-up job {warm.engine_wall_s:.4f} s)", file=sys.stderr)

    result = dict(device=dict(platform=devs[0].platform,
                              kind=devs[0].device_kind, count=len(devs)))
    if args.trace == 0:
        window = jobsmod.closed_loop(jobs, args.seconds)
        result["device"]["memory_peak_bytes"] = peak_bytes(devs)
        values = dict(
            sim_s_per_s=window.sim_s / window.wall_s,
            peak_hbm_mb=result["device"]["memory_peak_bytes"] / 1e6,
            setup_s=setup_s)
        metric_defs = cell.end_to_end
        done = window.jobs
        print(f"bench: {len(done)} jobs in {window.wall_s:.4f} s, walls "
              f"{[round(j.wall_s, 4) for j in done]}", file=sys.stderr)
    else:
        ctx = traced_job(jobs, sim)
        result["device"]["memory_peak_bytes"] = peak_bytes(devs)
        red = ctx["reduction"]
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        values = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], bench_dir)(ctx)
            if v is not None:
                values[m["name"]] = v
        metric_defs = cell.per_layer
        done = [ctx["job"]]
        result["breakdown"] = dict(
            device_ops=[[n, s] for n, s in red.top_ops[:9]] + [[
                trace.UNATTRIBUTED,
                red.phase_s.get(trace.UNATTRIBUTED, 0.0)]],
            idle_gaps=[[n, s] for n, s in red.idle_gaps])
        total = sum(red.phase_s.values()) or 1.0
        print("bench: share of device op time by phase: " + ", ".join(
            f"{p} {v / total:.4f}" for p, v in sorted(
                red.phase_s.items(), key=lambda kv: -kv[1])),
            file=sys.stderr)
    units = {m["name"]: m["unit"] for m in metric_defs}
    metrics = {k: dict(value=v, unit=units[k]) for k, v in values.items()
               if k in units}
    del sim, jobs
    correct, checks = check(cell, done, args.seed, bench_dir)
    for j in done:
        for f in j.failures:
            print(f"bench: invariant failed: {f}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    out = dict(correct=correct, attempted=len(done),
               failed=sum(1 for j in done if j.failures), metrics=metrics)
    out.update(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

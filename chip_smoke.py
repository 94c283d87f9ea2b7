#!/usr/bin/env python3
"""Bring-up smoke test: the simulator's main path on one TPU chip.

    python3 chip_smoke.py

Drives ``Simulation.run`` and ``Simulation.run_batch``, the entry points
users call, at the published sizes, in one process:

a. Table 2 ``case1b`` (10^6 requests, 1 service x 1,000 instances,
   8,000-slot pool, 685 ticks), run twice with one seed: the two runs must
   give identical digests.  A CPU reference in the same process (seeds 0
   and 1) bounds the chip's QoS statistics.
b. SockShop (13 services, call chains up to 4 deep, the paper's 10-node
   cluster, 300 clients, 600 s) with 2 replicas spread over the nodes, the
   network fabric, chaos faults and streamed telemetry, so Derive,
   Transit, Disruption and the io_callback flush run on the chip.  The
   sampled traces are rebuilt and must reproduce the engine's responses
   exactly; the graph-level check runs the tropical kernel.  CPU reference
   as in a.
c. An 8-point HS load sweep through ``run_batch`` (the setup of
   ``benchmarks/bench_scaling.sweep8_demo``); reports whether point 0
   equals a solo run bit for bit.
d. Table 2 ``case2b`` (50,000 services, 262,144-slot pool), run once.

Every chip run must conserve cloudlets exactly (spawned = finished +
in flight + dropped) and admit exactly ``num_limit`` requests where the
case sets one.  Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any failed phase or check exits non-zero, and so does a run where JAX
finds no TPU: nothing here falls back to the CPU.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax
import numpy as np

from benchmarks.bench_capacity import CASES, build_case
from benchmarks.bench_scaling import FIG11_KNOBS
from repro.configs import sockshop
from repro.core import batch_item, policies, register, summarize
from repro.core.types import CL_FREE
from repro.obs import export, spans

# What a run must find, and the sizes it runs at.  Tests rehearse the
# script on the CPU at a tiny size by replacing these.
PLATFORM = "tpu"
CASE1B = CASES["case1b"]
CASE2B = CASES["case2b"]
SOCKSHOP = dict(n_clients=300, duration_s=600.0)
SWEEP = dict(points=8, loads=(200, 1100), duration_s=600.0)

# QoS statistics the chip must reproduce within the seed-to-seed spread.
QOS_STATS = ("completed_requests", "failed_requests", "avg_response_ms",
             "p95_response_ms", "cloudlets_dropped")


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def device_info() -> dict:
    dev = jax.devices()[0]
    return dict(platform=dev.platform, device_kind=dev.device_kind)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def phase_line(phase: str, res, ticks: int, **extra) -> dict:
    return dict(phase=phase, **device_info(), ticks=ticks,
                wall_s=res.wall_time_s, compile_s=res.compile_time_s,
                ticks_per_s=ticks / res.wall_time_s,
                peak_bytes_in_use=peak_bytes(), **extra)


def digest(state) -> str:
    """Digest of the request responses and every counter."""
    h = hashlib.sha256(np.asarray(state.requests.response).tobytes())
    for x in jax.tree_util.tree_leaves(state.counters):
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()[:16]


def check_invariants(what: str, state, num_limit: int | None) -> None:
    """Exact integer conservation of cloudlets and admitted requests."""
    spawned = int(state.counters.spawned)
    finished = int(state.counters.finished)
    in_flight = int((np.asarray(state.cloudlets.status) != CL_FREE).sum())
    dropped = int(state.fstats.failed_attempts)
    require(spawned == finished + in_flight + dropped,
            f"{what}: spawned {spawned} != finished {finished} + in flight "
            f"{in_flight} + dropped {dropped}")
    admitted = int(state.requests.count)
    if num_limit is not None:
        require(admitted == num_limit,
                f"{what}: admitted {admitted} requests, num_limit "
                f"{num_limit}")
    else:
        written = int((np.asarray(state.requests.api) >= 0).sum())
        require(admitted == written,
                f"{what}: admitted {admitted} requests, {written} recorded")


def compare_with_cpu(phase: str, sim, res, make_sim) -> None:
    """The chip's QoS statistics against a CPU reference run of the same
    deployment in this process: |chip - CPU seed 0| must not exceed
    |CPU seed 0 - CPU seed 1| for any statistic."""
    with jax.default_device(jax.devices("cpu")[0]):
        cpu_sim = make_sim()
        ref = [cpu_sim.run(seed=s) for s in (0, 1)]
    for s, r in enumerate(ref):
        check_invariants(f"{phase} CPU seed {s}", r.state,
                         _num_limit(cpu_sim))
    q_chip = summarize(sim, res)
    q0, q1 = (summarize(cpu_sim, r) for r in ref)
    rows = {}
    for stat in QOS_STATS:
        chip, c0, c1 = (getattr(q, stat) for q in (q_chip, q0, q1))
        rows[stat] = dict(chip=chip, cpu_seed0=c0, cpu_seed1=c1,
                          chip_minus_cpu=abs(chip - c0),
                          seed0_minus_seed1=abs(c0 - c1))
    emit(dict(phase=f"{phase}/chip_vs_cpu", cpu_wall_s=ref[0].wall_time_s,
              stats=rows))
    wide = [s for s, r in rows.items()
            if r["chip_minus_cpu"] > r["seed0_minus_seed1"]]
    require(not wide, f"{phase}: chip-vs-CPU difference exceeds the "
                      f"seed-0-vs-seed-1 CPU difference for {wide}")


def _num_limit(sim) -> int | None:
    limit = sim.params.num_limit
    return None if limit == 2 ** 31 - 1 else limit


def capacity_case(case) -> object:
    n_requests, n_services, replicas, _, fanout = case
    return build_case(n_requests, n_services, replicas, fanout)[0]


def phase_case1b() -> None:
    sim = capacity_case(CASE1B)
    first, second = sim.run(seed=0), sim.run(seed=0)
    for r in (first, second):
        check_invariants("case1b", r.state, _num_limit(sim))
    d1, d2 = digest(first.state), digest(second.state)
    emit(phase_line("a.case1b", first, sim.params.n_ticks,
                    wall_s_rerun=second.wall_time_s, digest=d1,
                    digest_rerun=d2, deterministic=d1 == d2))
    require(d1 == d2, f"case1b: two runs with one seed differ ({d1} {d2})")
    compare_with_cpu("a.case1b", sim, first, lambda: capacity_case(CASE1B))


def sockshop_sim():
    return sockshop.make_sim(**SOCKSHOP, replicas=2,
                             placement_policy=policies.PLACE_SPREAD,
                             network="fabric", faults="chaos",
                             telemetry="stream")


def phase_sockshop() -> None:
    sim = sockshop_sim()
    with export.collecting() as rows:
        first = sim.run(seed=0)
    second = sim.run(seed=0)
    for r in (first, second):
        check_invariants("sockshop", r.state, _num_limit(sim))
    export.validate_rows(rows.rows)
    require(len(rows.rows) > 0, "sockshop: no telemetry rows streamed")
    checks = spans.verify_traces(first.state, sim.graph,
                                 int(sim.app.succ.shape[1]))
    eligible = [c for c in checks if not c.failed and c.retry_free]
    graphed = [c for c in eligible if c.graph is not None]
    d1, d2 = digest(first.state), digest(second.state)
    emit(phase_line("b.sockshop", first, sim.params.n_ticks,
                    wall_s_rerun=second.wall_time_s, digest=d1,
                    digest_rerun=d2, deterministic=d1 == d2,
                    telemetry_rows=len(rows.rows), traces=len(checks),
                    traces_checked=len(eligible),
                    traces_graph_checked=len(graphed)))
    require(d1 == d2, f"sockshop: two runs with one seed differ ({d1} {d2})")
    require(len(eligible) >= 5, f"sockshop: only {len(eligible)} sampled "
                                "retry-free traces to check")
    inexact = [c.req for c in eligible if not c.exact]
    require(not inexact, f"sockshop: traces of requests {inexact[:10]} do "
                         "not reproduce the engine's response exactly")
    require(len(graphed) > 0, "sockshop: no trace reached the graph-level "
                              "critical-path check")
    off = [c.req for c in graphed
           if not np.isclose(float(c.graph), float(c.response),
                             rtol=1e-5, atol=1e-5)]
    require(not off, f"sockshop: graph-level critical path disagrees with "
                     f"the response for requests {off[:10]}")
    compare_with_cpu("b.sockshop", sim, first, sockshop_sim)


def sweep_sim(params=None):
    """The sweep8 SockShop deployment, sized for the largest load;
    ``params`` replaces its SimParams (one sweep point run solo)."""
    sim = sockshop.make_sim(n_clients=max(SWEEP["loads"]),
                            duration_s=SWEEP["duration_s"],
                            scaling_policy=policies.SCALE_HORIZONTAL,
                            **FIG11_KNOBS)
    if params is None:
        return sim
    return register(sockshop.app_spec(sockshop.CALIBRATED["mi_scale"]),
                    sockshop.instance_spec(FIG11_KNOBS["share"]),
                    caps=sim.caps, params=params, vm_mips=sim.vm_mips,
                    vm_ram=sim.vm_ram)


def phase_sweep() -> None:
    sim = sweep_sim()
    loads = [int(x) for x in np.linspace(*SWEEP["loads"], SWEEP["points"])]
    points = [dataclasses.replace(sim.params, n_clients=nc,
                                  spawn_rate=nc / 30.0) for nc in loads]
    batch = sim.run_batch(points)
    for b in range(len(points)):
        check_invariants(f"sweep point {b}", batch_item(batch, b).state,
                         _num_limit(sim))
    solo = sweep_sim(points[0]).run()
    check_invariants("sweep solo", solo.state, _num_limit(sim))
    item = batch_item(batch, 0)
    got = np.asarray(item.state.requests.response)
    want = np.asarray(solo.state.requests.response)
    same = (digest(item.state) == digest(solo.state)
            and all(np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(jax.tree_util.tree_leaves(item.trace),
                                    jax.tree_util.tree_leaves(solo.trace))))
    emit(phase_line("c.sweep", batch, sim.params.n_ticks,
                    points=len(points), loads=loads,
                    point_ticks_per_s=len(points) * sim.params.n_ticks
                    / batch.wall_time_s,
                    solo_wall_s=solo.wall_time_s,
                    solo_compile_s=solo.compile_time_s,
                    batch_over_solo=batch.wall_time_s / solo.wall_time_s,
                    point0_equals_solo=same,
                    point0_max_abs_response_diff=float(
                        np.abs(got - want).max())))


def phase_case2b() -> None:
    sim = capacity_case(CASE2B)
    res = sim.run(seed=0)
    check_invariants("case2b", res.state, _num_limit(sim))
    emit(phase_line("d.case2b", res, sim.params.n_ticks,
                    spawned=int(res.state.counters.spawned),
                    finished=int(res.state.counters.finished)))


def main() -> None:
    devices = jax.devices()
    require(devices[0].platform == PLATFORM,
            f"JAX found no {PLATFORM} device ({devices}); this script never "
            "carries on without one")
    phase_case1b()
    phase_sockshop()
    phase_sweep()
    phase_case2b()
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()

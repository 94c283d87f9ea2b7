"""Paper Table 2 — simulation-capacity test (§6.1).

Four cases × two parameter sets, mirroring the paper's object counts
(requests / services / instances / cloudlets) on a single machine.  The
paper's engine is a Java event heap; ours is a vectorized tensor DES, so
we report wall time (excluding one-off XLA compilation, which is also
reported) against the paper's published runtimes.

Case structure (paper's counts; our topology interpretation in brackets):
  1: 1 service × 10³ instances, 10⁵/10⁶ requests → 1 cloudlet per request
  2: 5×10³/5×10⁴ parallel services (star fan-out), 10³ requests
     → 5×10⁶/5×10⁷ cloudlets [paper lists "instances 1" = 1 replica/service]
  3: 10²/10³ services × 3 replicas, 10⁴ requests
  4: 5×10³ services × 3 replicas, 10³/10⁴ requests
"""
from __future__ import annotations

import numpy as np

from repro.core import SimCaps, SimParams, Simulation, InstanceTemplate
from repro.core.graph import build_graph

from .common import emit, header

PAPER_S = {  # running time in seconds from Table 2
    ("case1", 0): 1.95, ("case1", 1): 13.29,
    ("case2", 0): 0.84, ("case2", 1): 2.73,
    ("case3", 0): 0.94, ("case3", 1): 1.40,
    ("case4", 0): 4.58, ("case4", 1): 9.56,
}


def flat_services(n: int, mi: float) -> "ServiceGraph":
    """n independent services, one API entering all of them (star without
    a gateway node — fan-out happens at request generation)."""
    names = [f"s{i}" for i in range(n)]
    return build_graph(names, {}, [("api", names[0], 1.0)],
                       {nm: mi for nm in names}, d_max=1)


def build_case(n_requests, n_services, replicas, fanout=1,
               use_pallas_interpret=False, network=False, faults=False,
               chaos2=False, telemetry=False, slo=False):
    """Build a capacity Simulation sized to the Table 2 object counts;
    returns (sim, meta) where meta records the sizing decisions.

    ``network=True`` runs the same case with the network fabric enabled
    (DESIGN.md §6) on amply-provisioned NICs: the Transit phase executes
    every tick (client→entry payloads cross host ingress ports) without
    starving the workload, so the wall-time delta is the phase's overhead.

    ``faults=True`` enables the Disruption phase (DESIGN.md §7) with mild
    chaos (long MTBF, quick MTTR, retries on): the full failure/retry/
    breaker machinery runs every tick without collapsing throughput, so
    the wall-time delta is the phase's overhead (target ≤ 1.3×).

    ``chaos2=True`` layers the second-generation gray-failure machinery
    (§7.1) on top of ``faults``: fail-slow episodes, NIC brownout spread,
    zone-correlated draws over a 4-domain cluster, partition draws and
    per-replica outlier ejection all sample every tick, so the delta over
    the fault-free case prices the FULL chaos surface (same ≤ 1.3×
    target, tracked as ``<tag>+chaos2``).

    ``telemetry=True`` streams observability (DESIGN.md §9): per-window
    metric rows flushed through the io_callback tap every 16 ticks plus
    1-in-100 span sampling — the delta over the telemetry-off case is
    the observation cost (target ≤ 1.05×, tracked as ``<tag>+obs``).

    ``slo=True`` (implies ``telemetry``) additionally compiles the
    Alerting stage (DESIGN.md §10) with an ENABLED run-wide objective, so
    the SLI accumulate, window seal, burn rules, state machine and event
    ring all execute every tick — the delta over ``+obs`` is the alert
    plane's cost (tracked as ``<tag>+slo``, target ≤ 1.1× telemetry-off:
    the SLI scatter-add is real per-tick pool work, not pure
    observation).
    """
    mi = 50.0
    if fanout > 1:
        graph = flat_services(n_services, mi)
        api_entries = [[f"s{i}" for i in range(n_services)]]
    else:
        graph = flat_services(n_services, mi)
        api_entries = None

    n_inst = n_services * replicas
    n_vms = max(n_inst // 64, 4)
    dt = 0.5
    fanout = max(fanout, 1)
    avg_wait_ticks = 4.0 / dt

    # Admission sizing: pick k_fire (requests admitted per tick) so the
    # active-cloudlet pool holds ~2 ticks of arrivals with 2× head-room,
    # then give the run enough ticks to admit everything + drain.
    target_ticks = 500
    k_fire = max(int(np.ceil(n_requests / target_ticks)), 1)
    if 5 * k_fire * fanout > 2 * (1 << 18):
        k_fire = max(2 * (1 << 18) // (5 * fanout), 1)
    pool = int(min(max(4 * k_fire * fanout, 1 << 12), 1 << 18))
    nc = int(min(max(k_fire * avg_wait_ticks, 64), 1 << 16))
    fire_rate = min(k_fire, nc / avg_wait_ticks)       # requests per tick
    n_ticks = int(n_requests / fire_rate * 1.25) + 60
    duration = n_ticks * dt

    caps = SimCaps(
        n_clients=nc,
        max_requests=n_requests + nc + 8,
        max_cloudlets=pool,
        max_instances=n_inst,
        n_vms=n_vms,
        d_max=1,
        max_replicas=replicas,
        k_fire=k_fire,
    )
    fault_kw = dict(
        faults="chaos", host_mtbf_s=duration * 2.0, host_mttr_s=2 * dt,
        inst_kill_rate=0.0, retry_timeout_s=20 * duration, retry_budget=2,
    ) if (faults or chaos2) else {}
    if chaos2:
        # mild gray chaos: every §7.1 stream samples each tick without
        # collapsing throughput (rates sized to a handful of episodes)
        fault_kw.update(
            host_slow_mtbf_s=duration, host_slow_mttr_s=4 * dt,
            host_slow_factor=0.5, nic_degrade_spread=0.2,
            zone_slow_rate=1.0 / duration,
            zone_partition_rate=1.0 / duration,
            zone_partition_mttr_s=4 * dt,
            eject_err_thresh=0.8, eject_cooldown_s=4 * dt)
    tel_kw = dict(
        telemetry="stream", tel_window_ticks=16, tel_windows=8,
        tel_span_k=100, tel_span_cap=4096,
        # staging budget: ~15 sampled finishers/tick expected at case1b —
        # without it the 4096-slot ring re-inflates the per-tick span
        # build the rank compaction exists to avoid
        tel_span_tick_cap=64,
    ) if (telemetry or slo) else {}
    if slo:
        tel_kw.update(alerting="burn", slo_budget=0.05,
                      slo_short_wins=2, slo_long_wins=4,
                      slo_for_ticks=2, slo_event_cap=256)
    params = SimParams(
        dt=dt, n_ticks=n_ticks, n_clients=nc,
        spawn_rate=nc / 5.0, wait_lo=2.0, wait_hi=6.0,
        num_limit=n_requests, seed=0,
        use_pallas_tick=use_pallas_interpret,
        pallas_interpret=use_pallas_interpret,
        network="fabric" if network else "uniform",
        # ample per-host NICs: the phase runs, the workload doesn't starve
        nic_egress_mbps=10_000.0, nic_ingress_mbps=10_000.0,
        **fault_kw, **tel_kw,
    )
    # Instance speed: each tick's per-instance batch drains in ~0.4 ticks,
    # keeping residence ≈ 2 ticks and utilization < 1 (no blow-up).
    a_i = fire_rate * fanout / n_inst        # cloudlet arrivals/inst/tick
    mips = max(a_i, 0.4) * mi / (0.4 * dt)
    tmpl = InstanceTemplate(mips=mips, limit_mips=2 * mips,
                            ram=1.0, limit_ram=2.0, bw=100.0,
                            replicas=replicas)
    vm_mips = np.full(n_vms, 2.0 * mips * n_inst / n_vms + 1e4, np.float32)
    vm_ram = np.full(n_vms, 1e9, np.float32)
    host_zone = (np.arange(n_vms, dtype=np.int32) % 4 if chaos2 else None)
    sim = Simulation(graph, caps=caps, params=params, default_template=tmpl,
                     vm_mips=vm_mips, vm_ram=vm_ram,
                     api_entries=api_entries, host_zone=host_zone)
    meta = dict(n_requests=n_requests, n_services=n_services,
                replicas=replicas, n_instances=n_inst, n_ticks=n_ticks,
                pool=pool, k_fire=k_fire)
    return sim, meta


# Table 2 case registry: tag → (n_requests, n_services, replicas,
# cloudlets_per_request, fanout)
CASES = {
    "case1a": (10 ** 5, 1, 1000, 1, 1),
    "case1b": (10 ** 6, 1, 1000, 1, 1),
    "case2a": (10 ** 3, 5 * 10 ** 3, 1, 5 * 10 ** 3, 5 * 10 ** 3),
    "case2b": (10 ** 3, 5 * 10 ** 4, 1, 5 * 10 ** 4, 5 * 10 ** 4),
    "case3a": (10 ** 4, 10 ** 2, 3, 10 ** 2, 10 ** 2),
    "case3b": (10 ** 4, 10 ** 3, 3, 10 ** 3, 10 ** 3),
    "case4a": (10 ** 3, 5 * 10 ** 3, 3, 5 * 10 ** 3, 5 * 10 ** 3),
    "case4b": (10 ** 4, 5 * 10 ** 3, 3, 5 * 10 ** 3, 5 * 10 ** 3),
}


def perf_record(tag: str, backend: str = "jnp", scale: float = 1.0,
                network: bool = False, faults: bool = False,
                chaos2: bool = False, telemetry: bool = False,
                slo: bool = False) -> dict:
    """One BENCH_perf.json record: wall seconds + ticks/sec for a Table 2
    case.  ``scale`` shrinks the request count (pallas-interpret runs are
    orders of magnitude slower than compiled backends).  ``network=True``
    re-runs the case with the fabric's Transit phase on (case tagged
    ``<tag>+net``), ``faults=True`` with the Disruption phase on
    (``<tag>+faults``), ``chaos2=True`` with the full gray-failure
    surface on (``<tag>+chaos2``), ``telemetry=True`` with streaming
    observability on (``<tag>+obs``), ``slo=True`` with burn-rate
    alerting on top (``<tag>+slo``), so each phase's overhead is
    tracked PR-over-PR."""
    n_requests, n_services, replicas, cpr, fanout = CASES[tag]
    n_requests = max(int(n_requests * scale), 100)
    sim, meta = build_case(n_requests, n_services, replicas, fanout,
                           use_pallas_interpret=(backend
                                                 == "pallas-interpret"),
                           network=network, faults=faults, chaos2=chaos2,
                           telemetry=telemetry, slo=slo)
    res = sim.run()
    suffix = ("+net" if network else "") \
        + ("+chaos2" if chaos2 else ("+faults" if faults else "")) \
        + ("+slo" if slo else ("+obs" if telemetry else ""))
    return dict(
        case=tag + suffix, backend=backend, scale=scale,
        requests=int(res.state.requests.count),
        cloudlets=int(res.state.counters.spawned),
        n_services=n_services, n_instances=meta["n_instances"],
        n_ticks=meta["n_ticks"],
        wall_s=round(res.wall_time_s, 4),
        compile_s=round(res.compile_time_s, 4),
        ticks_per_s=round(meta["n_ticks"] / max(res.wall_time_s, 1e-9), 2),
        paper_s=PAPER_S.get((tag[:-1], 0 if tag.endswith("a") else 1)),
    )


def bytes_per_tick(tag: str, network: bool = False,
                   faults: bool = False, chaos2: bool = False) -> float:
    """Per-tick "bytes accessed" of the compiled scan (XLA cost_analysis)
    for a Table 2 case — the footprint metric behind the mode-keyed pool
    layout (DESIGN.md §2.2): wall clocks drift on shared containers, but
    the compiled program's byte traffic is deterministic, so the reclaim
    from dropping disabled-phase columns is tracked PR-over-PR without
    timing noise.  Compiles (cached) but never executes the case."""
    from repro.core.types import DynParams

    n_requests, n_services, replicas, cpr, fanout = CASES[tag]
    sim, meta = build_case(n_requests, n_services, replicas, fanout,
                           network=network, faults=faults, chaos2=chaos2)
    state = sim.init_state()
    dyn = DynParams.from_params(sim.params)
    compiled, _ = sim._get_compiled(state, dyn)
    ca = compiled.cost_analysis()
    return float(ca.get("bytes accessed", -1.0)) / meta["n_ticks"]


def run_case(tag, n_requests, n_services, replicas, cloudlets_per_req,
             paper_s, fanout=1):
    """Run one Table 2 case and emit the CSV rows."""
    sim, meta = build_case(n_requests, n_services, replicas, fanout)
    res = sim.run()
    st = res.state
    n_inst = meta["n_instances"]
    emit(f"table2/{tag}/requests", int(st.requests.count), n_requests)
    emit(f"table2/{tag}/cloudlets", int(st.counters.spawned),
         cloudlets_per_req * n_requests)
    emit(f"table2/{tag}/finished", int(st.counters.finished), "",
         f"dropped={int(st.counters.dropped_cloudlets)}")
    emit(f"table2/{tag}/wall_s", f"{res.wall_time_s:.2f}", f"{paper_s:.2f}",
         f"compile_s={res.compile_time_s:.1f} "
         f"services={n_services} instances={n_inst}")
    return res


def main():
    header("Table 2: capacity test (wall seconds, compile excluded)")
    # cases 1: requests-dominated; 2: services-dominated star fan-out;
    # 3: balanced 1:3 service:instance ratio; 4: high-instance scenarios
    for tag, (n_requests, n_services, replicas, cpr, fanout) in CASES.items():
        paper = PAPER_S[(tag[:-1], 0 if tag.endswith("a") else 1)]
        run_case(tag, n_requests, n_services, replicas, cpr, paper,
                 fanout=fanout)


if __name__ == "__main__":
    main()

"""Fault injection & resilience — the Disruption tick phase (DESIGN.md §7).

The paper's headline claim is "comprehensive and dynamic modeling" with
QoS-based feedback, but a fair-weather engine cannot express availability,
error rate or recovery behavior — the QoS dimensions that make microservice
architectures interesting (uqSim, arXiv:1911.02122, validates exactly these
failure/queueing dynamics; resilience experiments à la Clue are the largest
untouched scenario family).  ``faults="chaos"`` inserts a **Disruption**
phase between Generation and Transit:

* **Injection** — a seeded, fully tensorized fault schedule: hosts crash
  and recover with MTBF/MTTR rates, instances are killed at a Poisson rate,
  host NICs degrade to a capacity fraction; every rate travels in
  :class:`DynParams`, so ``run_batch`` sweeps chaos intensity without
  recompiling.  A host going down flips its instances to ``INST_DOWN`` and
  fails their in-flight cloudlets in ONE masked pass over the stacked pool.
* **Resilience** — failed RPC attempts consult the per-service-edge retry
  policy (budget + per-attempt timeout); retries respawn through the
  existing two-scatter spawn path (``pool.scatter_pool``) with an attempt
  counter column, so a mass-kill wave frees and recycles slots in the same
  tick.  A per-edge circuit breaker (error-rate EMA trips open → fail-fast,
  half-open probe after a cooldown) is pure status masks — no control flow
  in the scan.  Exhausted retries propagate to the owning request as a
  *failed completion*.  A retry re-sends the call it replaces, on the
  same edge id: whether a call is made at all (per-API call trees,
  calls with p < 1, DESIGN.md §2.5) was settled when Derive spawned it,
  so the per-edge tables keep their meaning under routed graphs.
* **Feedback** — :class:`FaultStats` (availability, error rate, retry
  amplification, observed MTTR) joins the QoS report; HS scale-out and
  migration place replicas only on up hosts.

``faults="none"`` (default) compiles the exact pre-faults program — pinned
bit-identical by the golden digests in tests/test_network.py, the same
pattern ``network="uniform"`` uses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..analysis import streams
from . import network as netmod
from .app import AppStatic
from ..analysis.annotate import collide
from .pool import (assign_free_slots, scatter_pool, segment_rank,
                   segment_sum as _segsum)
from .types import (ALERT_FIRING, CL_EXEC, CL_FREE, CL_TRANSIT, CL_WAITING,
                    DynParams, FaultState, INST_DOWN, INST_DRAIN, INST_FREE,
                    INST_ON, SimCaps, SimParams, SimState)


def _p_rate(rate_per_s: jnp.ndarray, dt: jnp.ndarray) -> jnp.ndarray:
    """Per-tick event probability of a Poisson process with the given rate
    (exact exponential form — stable for any dt, 0 at rate 0)."""
    return 1.0 - jnp.exp(-dt * rate_per_s)


def _p_mean_time(mean_s: jnp.ndarray, dt: jnp.ndarray) -> jnp.ndarray:
    """Per-tick probability for a mean-time parameter (MTBF/MTTR);
    ``inf`` disables the transition."""
    return 1.0 - jnp.exp(-dt / jnp.maximum(mean_s, 1e-9))


def edge_payload_tables(app: AppStatic):
    """Flattened per-edge payload stats aligned with the cloudlet ``edge``
    id: call edges first ([S*d_max], row-major), then client→entry edges
    ([A]) — the layout §7 shares with the retry/breaker tables."""
    mean = jnp.concatenate([app.payload_mean.reshape(-1),
                            app.api_payload_mean])
    std = jnp.concatenate([app.payload_std.reshape(-1),
                           app.api_payload_std])
    return mean, std


def disruption(state: SimState, app: AppStatic, caps: SimCaps,
               params: SimParams, dyn: DynParams, rng: jnp.ndarray,
               rng_len: jnp.ndarray, rng_net=None) -> SimState:
    """One Disruption tick: sample the fault schedule, fail doomed work,
    respawn retries, advance the circuit breakers (all masked tensor ops —
    the pool streams a constant number of times, DESIGN.md §2.2).
    """
    cl, inst, req = state.cloudlets, state.instances, state.requests
    fs, fst = state.fault, state.fstats
    i32, f32 = jnp.int32, jnp.float32
    H = fs.host_up.shape[0]
    I = inst.status.shape[0]
    C = cl.status.shape[0]
    E = fs.edge_err_ema.shape[0]
    R = req.api.shape[0]
    V = state.vms.mips.shape[0]
    t, dt = state.time, dyn.dt
    # Trace-time guard: the per-edge retry/breaker tables must cover every
    # edge id the app can emit (S*d_max call edges + one client→entry edge
    # per API) — an undersized table silently aliases breaker state via
    # clamped gathers.  zeros_state sizes E correctly when given
    # n_edges/n_apis; states built with a stale single-API default land
    # here.
    if int(app.n_edges) > E:
        raise ValueError(
            f"fault edge tables undersized: app emits edge ids up to "
            f"{int(app.n_edges) - 1} but FaultState holds {E} edges — "
            f"pass app=app (or n_edges/n_apis) to zeros_state")
    if int(app.host_zone.shape[0]) != H:
        raise ValueError(
            f"host_zone table must cover every host: app maps "
            f"{int(app.host_zone.shape[0])} hosts but the cluster has {H} — "
            f"pass n_hosts (or host_zone) to build_app")

    k_host, k_inst, k_nic = streams.split(
        rng, 3, names=("host", "inst", "nic"))
    # Gray-failure streams are folded off the tick key rather than widening
    # the split above: jax.random.split is NOT prefix-stable, so one extra
    # child would perturb every pre-existing chaos stream and break the
    # pinned chaos goldens.  The whole derivation tree is now pinned by
    # the stream-topology digest test (repro/analysis/streams.py).
    k_slow, k_sev, k_zone, k_zslow, k_part = streams.split(
        streams.fold_in(rng, 1, name="gray"), 5,
        names=("slow", "sev", "zone", "zslow", "part"))

    # --- correlated failure domains (zone draws, DESIGN.md §7.1) ---------
    # One uniform draw per *zone slot* ([H] slots bound Z); a firing draw
    # downs (or slows) every host mapped to that zone.  Unused slots are
    # masked out so the fired-zone counter stays meaningful.
    hz = app.host_zone
    zone_used = jnp.zeros((H,), bool).at[hz].set(True)
    zone_down = zone_used & (jax.random.uniform(k_zone, (H,))
                             < _p_rate(dyn.zone_fault_rate, dt))
    zone_slow = zone_used & (jax.random.uniform(k_zslow, (H,))
                             < _p_rate(dyn.zone_slow_rate, dt))

    # --- host crash / recovery (MTBF / MTTR) ---------------------------
    up = fs.host_up > 0
    u_h = jax.random.uniform(k_host, (H,))
    crash = up & ((u_h < _p_mean_time(dyn.host_mtbf_s, dt)) | zone_down[hz])
    recover = ~up & (u_h < _p_mean_time(dyn.host_mttr_s, dt))
    up_new = (up & ~crash) | recover

    # --- host fail-slow episodes (degraded MIPS, MTBF/MTTR style) --------
    slow = fs.host_slow > 0
    u_sl = jax.random.uniform(k_slow, (H,))
    slow_start = ~slow & up_new \
        & ((u_sl < _p_mean_time(dyn.host_slow_mtbf_s, dt)) | zone_slow[hz])
    slow_end = slow & (u_sl < _p_mean_time(dyn.host_slow_mttr_s, dt))
    # a crashing host ends its episode: it restarts healthy
    slow_new = ((slow & ~slow_end) | slow_start) & up_new

    # --- NIC degradation (capacity fraction while degraded) -------------
    ok = fs.nic_ok > 0
    u_n = jax.random.uniform(k_nic, (H,))
    degrade = ok & (u_n < _p_rate(dyn.nic_degrade_rate, dt))
    fix = ~ok & (u_n < _p_mean_time(dyn.nic_mttr_s, dt))
    ok_new = (ok & ~degrade) | fix
    # Brownout severity is sampled once per degradation from
    # U[factor − spread, factor + spread] ∩ [0, 1] and held for the whole
    # episode; Transit multiplies NIC capacity by the stored factor.
    sev = jnp.clip(dyn.nic_degrade_factor
                   + (2.0 * jax.random.uniform(k_sev, (H,)) - 1.0)
                   * dyn.nic_degrade_spread, 0.0, 1.0)
    nic_factor = jnp.where(degrade, sev,
                           jnp.where(fix, 1.0, fs.nic_factor))

    # --- partial partitions (zone-pair link cuts) ------------------------
    # Symmetric [Z, Z] mask updated on the strictly-upper triangle (one
    # draw per unordered pair) and mirrored; Transit zeroes the capacity
    # of cut transfers in the water-fill instead of crashing anything.
    cut = fs.zone_cut > 0
    u_p = jax.random.uniform(k_part, (H, H))
    upper = jnp.triu(jnp.ones((H, H), bool), 1)
    pair_used = upper & zone_used[:, None] & zone_used[None, :]
    p_open = pair_used & ~cut & (u_p < _p_rate(dyn.zone_partition_rate, dt))
    p_heal = cut & upper & (u_p < _p_mean_time(dyn.zone_partition_mttr_s, dt))
    cut_upper = (cut & upper & ~p_heal) | p_open
    zone_cut_new = (cut_upper | cut_upper.T).astype(i32)

    # --- instance transitions -------------------------------------------
    host_safe = jnp.maximum(inst.host, 0)
    host_down = (inst.host >= 0) & ~up_new[host_safe]
    on = inst.status == INST_ON
    u_i = jax.random.uniform(k_inst, (I,))
    killed = on & (u_i < _p_rate(dyn.inst_kill_rate, dt))
    goes_down = on & (host_down | killed)
    # a draining pod on a crashed node is simply gone: free the slot and
    # release its VM share (its queue is wiped below anyway)
    drain_dies = (inst.status == INST_DRAIN) & host_down
    restarts = (inst.status == INST_DOWN) & ~host_down \
        & (u_i < _p_mean_time(dyn.inst_mttr_s, dt))

    status_new = jnp.where(goes_down, INST_DOWN, inst.status)
    status_new = jnp.where(drain_dies, INST_FREE, status_new)
    status_new = jnp.where(restarts, INST_ON, status_new)
    dead_now = goes_down | drain_dies

    rel_m = _segsum(jnp.where(drain_dies, inst.mips, 0.0), inst.vm, V)
    rel_r = _segsum(jnp.where(drain_dies, inst.ram, 0.0), inst.vm, V)
    vms = state.vms._replace(mips_used=state.vms.mips_used - rel_m,
                             ram_used=state.vms.ram_used - rel_r)

    # --- fail doomed in-flight work (one masked pass over the pool) ------
    active = cl.status != CL_FREE
    ci = jnp.maximum(cl.inst, 0)
    inst_dead = (cl.inst >= 0) & (dead_now[ci]
                                  | (status_new[ci] == INST_DOWN))
    # Per-attempt timeout: the per-edge registry value ("timeouts" spec
    # keys) when set, else the run-wide sweepable dyn.retry_timeout_s —
    # mirroring the per-edge retry-budget resolver below.
    e_safe = jnp.maximum(cl.edge, 0)
    tmo = jnp.where(app.edge_timeout[e_safe] >= 0,
                    app.edge_timeout[e_safe], dyn.retry_timeout_s)
    doomed = inst_dead | ((t - cl.arrival) > tmo)
    if "src_host" in cl.layout:
        # fabric mode only: an in-flight transfer whose source host died
        # loses its payload (uniform mode has no TRANSIT work by
        # construction, and no src_host column to read)
        doomed = doomed | ((cl.status == CL_TRANSIT) & (cl.src_host >= 0)
                           & ~up_new[jnp.maximum(cl.src_host, 0)])
    organic = active & doomed

    # circuit-breaker status masks (state machine documented in FaultState)
    open_m = fs.edge_open_until > t
    half_m = (fs.edge_open_until > 0) & ~open_m
    cl_open = (cl.edge >= 0) & open_m[e_safe]
    # fail-fast only calls spawned since the previous Disruption pass: an
    # open breaker blocks NEW calls, it never cancels established work
    fresh = cl.arrival >= t - dt
    failfast = active & ~organic & cl_open & fresh & (cl.status != CL_EXEC)

    failed = organic | failfast
    budget = jnp.where(app.edge_retry[e_safe] >= 0, app.edge_retry[e_safe],
                       dyn.retry_budget)
    can_retry = organic & (cl.attempt < budget) & ~cl_open
    # Per-tick retry admission budget (SimCaps.k_retry): the respawn wave
    # is a K-rank scatter like gen_spawn's k_fire, so its cost must not
    # scale with the whole pool; failures past the budget fail permanently
    # (a genuine mass-kill wave mostly fits — the auto budget is C/8).
    K_cap = caps.k_retry if caps.k_retry > 0 else min(C, max(256, C // 8))
    K_cap = min(K_cap, C)
    retry_rank = jnp.cumsum(can_retry.astype(i32)) - 1
    can_retry = can_retry & (retry_rank < K_cap)
    permanent = failed & ~can_retry

    # n_exec stays leak-free through a mass-kill wave: failures on still-up
    # instances (timeouts) decrement, dead instances reset to zero (all of
    # their executing cloudlets are in the failed set).
    exec_failed = failed & (cl.status == CL_EXEC)
    dec = _segsum(exec_failed.astype(i32),
                  jnp.where(exec_failed, cl.inst, -1), I)
    n_exec_new = jnp.where((status_new == INST_DOWN) | drain_dies, 0,
                           inst.n_exec - dec)

    instances = inst._replace(
        status=status_new,
        service=jnp.where(drain_dies, -1, inst.service),
        vm=jnp.where(drain_dies, -1, inst.vm),
        host=jnp.where(drain_dies, -1, inst.host),
        mips=jnp.where(drain_dies, 0.0, inst.mips),
        ram=jnp.where(drain_dies, 0.0, inst.ram),
        n_exec=n_exec_new,
        util_ema=jnp.where(goes_down | drain_dies, 0.0,
                           jnp.where(restarts, 0.5, inst.util_ema)),
    )

    # --- permanent failures propagate to the owning request --------------
    # finish is scatter-maxed with the failure time so the request's
    # response (finish - arrival) stays ≥ 0 when it completes as failed.
    # several cloudlets of one request can fail in the same wave —
    # accumulation into the shared request row is intended
    rdst = jnp.where(permanent & (cl.req >= 0), cl.req, R)
    with collide("request_fail_counts"):
        requests = req._replace(
            outstanding=req.outstanding.at[rdst].add(-1, mode="drop"),
            failed=req.failed.at[rdst].max(jnp.uint8(1), mode="drop"),
            finish=req.finish.at[rdst].max(t, mode="drop"),
        )

    # --- free failed slots (masked column writes, no per-field scatters) --
    cl2 = cl.with_cols(status=jnp.where(failed, CL_FREE, cl.status),
                       inst=jnp.where(failed, -1, cl.inst))

    state = state._replace(cloudlets=cl2, instances=instances, vms=vms,
                           requests=requests)

    # --- respawn retries through the two-scatter spawn path ---------------
    # Every retry descriptor's own slot was just freed and the wave is
    # pre-capped to K_cap, so free ≥ wanted and the wave can never drop
    # (a dropped retry would strand its request's outstanding count).
    asg = assign_free_slots(cl2.status == CL_FREE, can_retry,
                            k_static=K_cap)
    Ka = asg.dst.shape[0]
    svc_new = cl.service[asg.src]
    req_new = cl.req[asg.src]
    edge_new = cl.edge[asg.src]
    att_new = cl.attempt[asg.src] + 1
    dep_new = cl.depth[asg.src]
    sin_new = cl.src_inst[asg.src]
    noise = jax.random.normal(rng_len, (Ka,), f32)
    length = jnp.maximum(app.len_mean[svc_new] + app.len_std[svc_new] * noise,
                         1.0)

    if rng_net is None:                  # uniform transport mode
        status_sp, inst_sp = CL_WAITING, -1
        src_host_sp, bytes_sp = -1, 0.0
        rr = state.rr
    else:                                # fabric mode: re-address + payload
        k_lb, k_pay = streams.split(rng_net, names=("lb", "payload"))
        tgt, rr = netmod.pick_replicas(svc_new, asg.live, state, caps,
                                       params, k_lb)
        pay_mean, pay_std = edge_payload_tables(app)
        eg = jnp.maximum(edge_new, 0)
        payload = netmod.sample_payload(pay_mean[eg], pay_std[eg], k_pay)
        # src host re-derived from the caller instance (it may have
        # migrated); the retried transfer contends like the original did
        sh = jnp.where(sin_new >= 0,
                       instances.host[jnp.maximum(sin_new, 0)], -1)
        dh = jnp.where(tgt >= 0, instances.host[jnp.maximum(tgt, 0)], -1)
        loop = (tgt >= 0) & (sh >= 0) & (sh == dh)
        in_transit = (tgt >= 0) & ~loop
        status_sp = jnp.where(in_transit, CL_TRANSIT, CL_WAITING)
        inst_sp = tgt
        src_host_sp = jnp.where(in_transit, sh, -1)
        bytes_sp = jnp.where(in_transit, payload, 0.0)

    cloudlets = scatter_pool(
        cl2, asg,
        status=status_sp, req=req_new, service=svc_new, inst=inst_sp,
        wait_ticks=0, depth=dep_new, src_host=src_host_sp,
        attempt=att_new, edge=edge_new, src_inst=sin_new,
        length=length, rem=length,
        arrival=jnp.full((Ka,), 0.0, f32) + t, start=-1.0,
        rem_bytes=bytes_sp)

    rds2 = jnp.where(asg.live, req_new, R)
    with collide("spawn_request_counts"):
        requests = requests._replace(
            spawned=requests.spawned.at[rds2].add(1, mode="drop"))

    # --- circuit-breaker update (per edge, masks only) --------------------
    # Fail-fast failures are excluded from the EMA input: they are caused
    # by the breaker and would hold it open forever.
    org_e = _segsum(organic.astype(i32), jnp.where(organic, cl.edge, -1), E)
    succ_e = fs.edge_succ
    n_e = org_e + succ_e
    err = org_e.astype(f32) / jnp.maximum(n_e.astype(f32), 1.0)
    traffic = n_e > 0
    ema = jnp.where(traffic,
                    fs.edge_err_ema + dyn.cb_alpha * (err - fs.edge_err_ema),
                    fs.edge_err_ema)
    closed_m = fs.edge_open_until <= 0
    trip = closed_m & traffic & (ema > dyn.cb_err_thresh)
    reopen = half_m & (org_e > 0)
    close = half_m & (org_e == 0) & (succ_e > 0)
    open_until = jnp.where(trip | reopen, t + dyn.cb_cooldown_s,
                           jnp.where(close, 0.0, fs.edge_open_until))
    ema = jnp.where(close, 0.0, ema)   # clean slate after a healthy probe

    # --- per-replica outlier ejection (breaker-aware LB, §7.1) ------------
    # Same three-state machine as the edge breaker, but per instance and
    # enforced in the dispatch rank table (policies.eject_view) — a sick
    # replica is routed around instead of the whole edge failing fast.
    S = state.sched.svc_replicas.shape[0]
    org_i = _segsum(organic.astype(i32), jnp.where(organic, cl.inst, -1), I)
    succ_i = fs.inst_succ
    n_i = org_i + succ_i
    traffic_i = n_i > 0
    err_i = org_i.astype(f32) / jnp.maximum(n_i.astype(f32), 1.0)
    iema = jnp.where(traffic_i,
                     fs.inst_err_ema
                     + dyn.cb_alpha * (err_i - fs.inst_err_ema),
                     fs.inst_err_ema)
    mean_lat = fs.inst_lat_sum / jnp.maximum(succ_i.astype(f32), 1.0)
    lema = jnp.where(succ_i > 0,
                     fs.inst_lat_ema + dyn.cb_alpha * (mean_lat
                                                       - fs.inst_lat_ema),
                     fs.inst_lat_ema)
    # latency outlier = EMA above eject_lat_factor × the service's mean
    # over its ON replicas with signal (≥ 2 so a lone replica never
    # outlies itself)
    on_i = instances.status == INST_ON
    isvc_safe = jnp.maximum(instances.service, 0)
    sig = on_i & (lema > 0) & (instances.service >= 0)
    lat_sum_s = _segsum(jnp.where(sig, lema, 0.0),
                        jnp.where(sig, instances.service, -1), S)
    lat_cnt_s = _segsum(sig.astype(i32), jnp.where(sig, instances.service,
                                                   -1), S)
    svc_lat = lat_sum_s / jnp.maximum(lat_cnt_s.astype(f32), 1.0)
    # Alert-driven tightening (DESIGN.md §10): while any burn alert FIRES
    # on a replica's service, its ejection thresholds multiply by
    # dyn.slo_eject_tighten (< 1 tightens) — outliers get evicted sooner
    # exactly when the service is burning its error budget.  Tighten = 1.0
    # (the default) multiplies exactly, so the sixth golden combo stays
    # bit-identical; the alert state the stage reads is one tick old
    # (Disruption precedes Execute/Alerting in the tick).
    if params.telemetry == "stream" and params.alerting == "burn":
        firing_s = (state.alerts.astate == ALERT_FIRING).any(axis=1)
        tighten = jnp.where(firing_s[isvc_safe] & (instances.service >= 0),
                            dyn.slo_eject_tighten, 1.0)
    else:
        tighten = 1.0
    eff_err_thresh = dyn.eject_err_thresh * tighten
    eff_lat_factor = dyn.eject_lat_factor * tighten
    lat_trip = (dyn.eject_lat_factor > 0) & (lat_cnt_s[isvc_safe] >= 2) \
        & (lema > eff_lat_factor * svc_lat[isvc_safe])
    ej_open = fs.inst_eject_until > t
    ej_half = (fs.inst_eject_until > 0) & ~ej_open
    ej_closed = fs.inst_eject_until <= 0
    want = ej_closed & on_i & traffic_i \
        & ((iema > eff_err_thresh) | lat_trip)
    # last-replica guard: keep at least one admissible (ON, not-ejected)
    # replica per service — cap this tick's ejections at admissible − 1
    n_adm = _segsum((on_i & ~ej_open).astype(i32),
                    jnp.where(instances.service >= 0, instances.service, -1),
                    S)
    eject_rank = segment_rank(isvc_safe, want, S)
    trip_i = want & (eject_rank < jnp.maximum(n_adm[isvc_safe] - 1, 0))
    probe_fail = ej_half & (org_i > 0)
    probe_ok = ej_half & (org_i == 0) & (succ_i > 0)
    eject_until = jnp.where(trip_i | probe_fail, t + dyn.eject_cooldown_s,
                            jnp.where(probe_ok, 0.0, fs.inst_eject_until))
    iema = jnp.where(probe_ok, 0.0, iema)
    lema = jnp.where(probe_ok, 0.0, lema)
    # dead / restarted pods shed their ejection history: a fresh pod is
    # re-admitted clean
    gone = dead_now | restarts
    eject_until = jnp.where(gone, 0.0, eject_until)
    iema = jnp.where(gone, 0.0, iema)
    lema = jnp.where(gone, 0.0, lema)

    fault = FaultState(host_up=up_new.astype(i32), nic_ok=ok_new.astype(i32),
                       edge_open_until=open_until, edge_err_ema=ema,
                       edge_succ=jnp.zeros_like(succ_e),
                       host_slow=slow_new.astype(i32),
                       nic_factor=nic_factor,
                       zone_cut=zone_cut_new,
                       inst_err_ema=iema, inst_lat_ema=lema,
                       inst_eject_until=eject_until,
                       inst_succ=jnp.zeros_like(succ_i),
                       inst_lat_sum=jnp.zeros_like(fs.inst_lat_sum))

    counters = state.counters._replace(
        spawned=state.counters.spawned + asg.n_assigned)
    fstats = fst._replace(
        host_crashes=fst.host_crashes + jnp.sum(crash.astype(i32)),
        host_recoveries=fst.host_recoveries + jnp.sum(recover.astype(i32)),
        inst_kills=fst.inst_kills + jnp.sum(killed.astype(i32)),
        failed_attempts=fst.failed_attempts + jnp.sum(failed.astype(i32)),
        retries=fst.retries + asg.n_assigned,
        failfast=fst.failfast + jnp.sum(failfast.astype(i32)),
        breaker_trips=fst.breaker_trips + jnp.sum(trip.astype(i32)),
        down_time_s=fst.down_time_s + dt * jnp.sum((~up_new).astype(f32)),
        ejections=fst.ejections + jnp.sum(trip_i.astype(i32)),
        readmissions=fst.readmissions + jnp.sum(probe_ok.astype(i32)),
        zone_faults=fst.zone_faults + jnp.sum(zone_down.astype(i32))
        + jnp.sum(zone_slow.astype(i32)),
        partitions=fst.partitions + jnp.sum(p_open.astype(i32)),
        slow_episodes=fst.slow_episodes + jnp.sum(slow_start.astype(i32)),
        slow_time_s=fst.slow_time_s + dt * jnp.sum(slow_new.astype(f32)),
    )
    return state._replace(rr=rr, cloudlets=cloudlets, requests=requests,
                          counters=counters, fault=fault, fstats=fstats)

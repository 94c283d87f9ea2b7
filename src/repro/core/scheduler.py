"""Cloudlet scheduler phases (paper §4.2) + derivative spawning (§4.1.2).

Every tick runs, in order:

  ``gen_spawn``   — new requests fire root cloudlets at API entry services
  ``disruption``  — (chaos mode, core/faults.py) hosts crash/recover,
                    instances die, doomed work fails, retries respawn,
                    circuit breakers advance
  ``transit``     — (fabric mode, core/network.py) in-flight payloads share
                    host NICs max-min fairly; arrivals join the waiting queue
  ``dispatch``    — waiting→execution transition with load balancing
  ``execute``     — time-shared progress + finish detection + usage history
  ``derive``      — finished cloudlets spawn successors along the DAG
  ``complete``    — requests whose last cloudlet finished get a response

The waiting/execution/finished "queues" of the paper are status masks on
the active cloudlet buffer; the finished queue is folded into per-request
and per-service aggregates (DESIGN.md §2).

One-pass tick discipline (DESIGN.md §2.2): spawn waves write the stacked
cloudlet pool with two row scatters (``scatter_pool``), and the execution
phase folds progress plus every finish-side reduction into a single fused
op (``cloudlet_finish``, one stacked scatter per table), so the
``max_cloudlets`` buffer streams through memory a constant number of
times per tick regardless of how many statistics are maintained.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..analysis import streams
from . import network as netmod
from . import policies
from .app import AppStatic
from .generator import divide_rn
from .pool import (assign_free_slots, rank_select, scatter_pool,
                   segment_rank, segment_sum as _segsum)
from ..analysis.annotate import collide
from .types import (CL_EXEC, CL_FREE, CL_TRANSIT, CL_WAITING,
                    DynParams, INST_DRAIN, INST_FREE, INST_ON, SimCaps,
                    SimParams, SimState)


# ===========================================================================
# Generation: new requests + root cloudlets (paper Alg 1 + "Dispatching")
# ===========================================================================

class GenResult(NamedTuple):
    n_new_requests: jnp.ndarray


def gen_spawn(state: SimState, app: AppStatic, caps: SimCaps,
              fired: jnp.ndarray, api: jnp.ndarray,
              wait_proposal: jnp.ndarray, rng: jnp.ndarray, dyn: DynParams,
              params: SimParams | None = None, net_rng=None
              ) -> Tuple[SimState, GenResult]:
    """Allocate request slots for fired clients and spawn root cloudlets.

    With ``net_rng`` set (network fabric mode, DESIGN.md §6) each root
    cloudlet is addressed to a replica and enters TRANSIT carrying the
    API's request payload — the client is external, so the transfer
    contends only on the destination host's ingress NIC (src_host = -1).
    """
    req, cl, ctr = state.requests, state.cloudlets, state.counters
    R = req.api.shape[0]
    i32, f32 = jnp.int32, jnp.float32
    Nc = fired.shape[0]
    K = caps.k_fire if caps.k_fire > 0 else Nc
    K = min(K, Nc)
    E = app.api_entry.shape[1]

    rank = jnp.cumsum(fired.astype(i32)) - 1
    # Admission: per-tick budget AND the generator's numLimit (Alg 1) —
    # both enforced per client so a burst tick cannot overshoot the limit.
    in_budget = fired & (rank < K) & (req.count + rank < dyn.num_limit)
    slot = req.count + rank
    has_slot = in_budget & (slot < R)
    n_accept = jnp.sum(has_slot.astype(i32))
    n_pool_drop = jnp.sum((in_budget & ~has_slot).astype(i32))

    # Client wait update: accepted/pool-dropped clients rest; over-budget
    # clients retry next tick (backpressure); others count down.
    new_wait = jnp.where(
        in_budget, wait_proposal,
        jnp.where(fired, 0, jnp.maximum(state.clients.wait - 1, 0)))

    # ---- root cloudlet descriptors [K, E] ------------------------------
    # Compact accepted clients into rank order (they are the first
    # n_accept fired clients, so their rank among fired is their rank).
    client_of_rank = rank_select(has_slot, K)
    ranks = jnp.arange(K, dtype=i32)
    r_live = ranks < n_accept
    api_r = api[client_of_rank]                      # [K]
    req_slot_r = req.count + ranks                   # [K]

    svc_d = app.api_entry[api_r]                     # [K, E]
    n_ent = app.api_n_entry[api_r]                   # [K]
    valid = (r_live[:, None] & (jnp.arange(E)[None, :] < n_ent[:, None])
             & (svc_d >= 0)).reshape(-1)
    svc_flat = svc_d.reshape(-1)
    req_flat = jnp.broadcast_to(req_slot_r[:, None], (K, E)).reshape(-1)

    asg = assign_free_slots(cl.status == CL_FREE, valid)
    Ka = asg.dst.shape[0]
    svc_new = svc_flat[asg.src]          # rank-level gather (for sampling)
    # clamp is a no-op on live lanes (has_slot ⇒ slot < R, and only
    # has_slot descriptors are compacted into live ranks) but makes
    # req ∈ [-1, R-1] a pool-column invariant the verifier can carry
    req_new = jnp.minimum(req_flat[asg.src], R - 1)
    api_flat = jnp.broadcast_to(api_r[:, None], (K, E)).reshape(-1)
    api_new = api_flat[asg.src]
    # client→entry edge id: after the S*d_max call edges (resilience, §7)
    edge_new = app.n_services * app.succ.shape[1] + api_new
    noise = jax.random.normal(rng, (Ka,), f32)
    length = jnp.maximum(app.len_mean[svc_new] + app.len_std[svc_new] * noise,
                         1.0)

    if net_rng is None:                  # uniform mode (degenerate network)
        status_new, inst_new = CL_WAITING, -1
        src_host_new, bytes_new = -1, 0.0
        rr = state.rr
    else:                                # fabric mode: address + payload
        k_lb, k_pay = streams.split(net_rng, names=("lb", "payload"))
        tgt, rr = netmod.pick_replicas(svc_new, asg.live, state, caps,
                                       params, k_lb)
        payload = netmod.sample_payload(app.api_payload_mean[api_new],
                                        app.api_payload_std[api_new], k_pay)
        # No live replica yet → park in the waiting queue (dispatch
        # re-balances); clients are external, so no loopback fast path.
        status_new = jnp.where(tgt >= 0, CL_TRANSIT, CL_WAITING)
        inst_new = tgt
        src_host_new = -1
        bytes_new = jnp.where(tgt >= 0, payload, 0.0)

    # Fused spawn write: every i32 field in one scatter, every f32 field
    # in the other (columns outside this mode's layout are skipped).
    cloudlets = scatter_pool(
        cl, asg,
        status=status_new, req=req_new, service=svc_new, inst=inst_new,
        wait_ticks=0, depth=0, src_host=src_host_new,
        attempt=0, edge=edge_new, src_inst=-1,
        length=length, rem=length,
        arrival=jnp.full((Ka,), 0.0, f32) + state.time, start=-1.0,
        rem_bytes=bytes_new)

    # ---- write accepted requests -------------------------------------
    # The request pool is append-only: the r-th accepted request takes slot
    # req.count + r, so the accepted rows fill [count, count + n_accept)
    # and every request write is one read-modify-write of the W-row window
    # [s0, s0 + W) that holds them: W lanes of work however large R is.
    # A fresh slot still holds its zeros_state values
    # (outstanding=spawned=critical_len=0, response=-1, finish=0); finish
    # then grows purely via the execute-phase scatter-max (tfin ≥ arrival).
    W = min(K, R)
    s0 = jnp.clip(req.count, 0, R - W)
    # window row j holds rank j - shift; shift = count - s0 ∈ [0, W] since
    # count ≤ R (the clip states it for the index verifier)
    shift = jnp.clip(req.count - s0, 0, W)
    row_rank = jnp.arange(W, dtype=i32) - shift
    row_live = (row_rank >= 0) & (row_rank < n_accept)

    def by_row(per_rank):
        """[K] rank-order values → [W] window rows (0 in front of rank 0)."""
        padded = jnp.concatenate([jnp.zeros((W,), per_rank.dtype), per_rank])
        return lax.dynamic_slice(padded, (W - shift,), (W,))

    def rmw(col, fn):
        win = lax.dynamic_slice(col, (s0,), (W,))
        return lax.dynamic_update_slice(col, fn(win), (s0,))

    # Root cloudlets each request got: its entries that won a pool slot
    # (the r-th valid descriptor is assigned iff r < n_assigned).  Ranks
    # ≥ n_accept have no valid entries, so their rows add 0.
    got = valid & (jnp.cumsum(valid.astype(i32)) - 1 < asg.n_assigned)
    n_got = by_row(got.reshape(K, E).sum(axis=1, dtype=i32))
    api_w = by_row(api_r)
    t_now = state.time.astype(req.arrival.dtype)
    requests = req._replace(
        count=req.count + n_accept,
        api=rmw(req.api, lambda w: jnp.where(row_live, api_w, w)),
        arrival=rmw(req.arrival, lambda w: jnp.where(row_live, t_now, w)),
        outstanding=rmw(req.outstanding, lambda w: w + n_got),
        spawned=rmw(req.spawned, lambda w: w + n_got),
    )
    counters = ctr._replace(
        spawned=ctr.spawned + asg.n_assigned,
        dropped_cloudlets=ctr.dropped_cloudlets + asg.n_dropped,
        dropped_requests=ctr.dropped_requests + n_pool_drop,
    )
    state = state._replace(
        rr=rr, clients=state.clients._replace(wait=new_wait),
        requests=requests, cloudlets=cloudlets, counters=counters)
    return state, GenResult(n_new_requests=n_accept)


# ===========================================================================
# Dispatch: waiting → execution with load balancing (paper §4.2)
# ===========================================================================

def dispatch(state: SimState, app: AppStatic, caps: SimCaps,
             params: SimParams, dyn: DynParams, rng: jnp.ndarray,
             network: bool = False) -> SimState:
    cl, inst, sched = state.cloudlets, state.instances, state.sched
    C = cl.status.shape[0]
    I = inst.status.shape[0]
    S = app.n_services
    i32 = jnp.int32

    if network:
        # Fabric mode: transport is modeled by the Transit phase — a
        # waiting cloudlet has already crossed the network (or took the
        # loopback fast path) and is usually pre-addressed to a replica.
        waiting = cl.status == CL_WAITING
    else:
        # An RPC hop must traverse the network before it may be scheduled
        # (net_latency models client→service and service→service
        # transport) — the load-independent degenerate mode.
        waiting = (cl.status == CL_WAITING) & \
            (state.time + 1e-6 >= cl.arrival + dyn.net_latency)
    if params.faults == "chaos":
        # outlier ejection (§7.1): dispatch around OPEN-ejected replicas —
        # the exact identity view when nothing is ejected
        iof, reps = policies.eject_view(sched, state.fault.inst_eject_until,
                                        state.time)
    else:
        iof, reps = sched.inst_of_rank, sched.svc_replicas
    svc = jnp.where(waiting, cl.service, 0)
    replicas = reps[svc]                                    # [C]
    has_rep = waiting & (replicas > 0)
    rep_safe = jnp.maximum(replicas, 1)

    # Shared three-policy rank selection (policies.lb_rank) — dispatch
    # offsets round-robin by slot order; the fabric's spawn-time
    # addressing (network.pick_replicas) uses the same helper with an
    # FCFS wave-rank offset.
    rank = policies.lb_rank(
        params.lb_policy, state.rr, svc, rep_safe,
        jnp.arange(C, dtype=i32), rng,
        iof, inst.status, inst.n_exec, inst.mips)

    target = iof[svc, jnp.minimum(rank, caps.max_replicas - 1)]
    ok = has_rep & (target >= 0)
    tgt_safe = jnp.where(ok, target, 0)
    ok = ok & (inst.status[tgt_safe] == INST_ON)

    if network:
        # Honor the spawn-time address when the replica is still ON and
        # still serves this cloudlet's service (the slot may have been
        # freed and re-bound by scale-in/out while the payload was in
        # flight); otherwise fall through to the fresh load-balancing
        # decision computed above.
        pre = cl.inst
        pre_safe = jnp.maximum(pre, 0)
        use_pre = (waiting & (pre >= 0)
                   & (inst.status[pre_safe] == INST_ON)
                   & (inst.service[pre_safe] == cl.service))
        if params.faults == "chaos":
            # a replica ejected while the payload was in flight is not
            # honored either — re-balance to a healthy one
            use_pre = use_pre & ~(
                state.fault.inst_eject_until[pre_safe] > state.time)
        target = jnp.where(use_pre, pre, target)
        ok = ok | use_pre
        tgt_safe = jnp.where(ok, target, 0)

    if params.max_concurrent > 0:
        # Space-shared admission: FCFS rank within the target instance
        # must fit in the remaining concurrency budget (paper: unselected
        # cloudlets re-enter the waiting queue).  Prefix-sum ranking —
        # no sort on the hot path.
        intra = segment_rank(jnp.where(ok, target, I), ok, I + 1)
        cap_left = jnp.maximum(dyn.max_concurrent - inst.n_exec, 0)
        admit = ok & (intra < cap_left[tgt_safe])
    else:
        admit = ok

    # One pool-sized scatter: admissions per instance.  It both maintains
    # the incremental n_exec counter (execute no longer re-counts the
    # execution queue) and, reduced over the small instance table, yields
    # the per-service dispatch counts for the round-robin cursors.
    admit_per_inst = _segsum(admit.astype(i32),
                             jnp.where(admit, target, -1), I)
    if network:
        # Pre-addressed cloudlets already advanced the cursor at spawn
        # (pick_replicas); counting them again here would step the cursor
        # twice per RPC and pin round-robin traffic to one replica.
        lb_admit = admit & ~use_pre
        lb_per_inst = _segsum(lb_admit.astype(i32),
                              jnp.where(lb_admit, target, -1), I)
        disp_per_svc = _segsum(lb_per_inst, inst.service, S)
    else:
        disp_per_svc = _segsum(admit_per_inst, inst.service, S)
    rr = (state.rr + disp_per_svc) % jnp.maximum(sched.svc_replicas, 1)

    cloudlets = cl.with_cols(
        status=jnp.where(admit, CL_EXEC, cl.status),
        inst=jnp.where(admit, target, cl.inst),
        start=jnp.where(admit & (cl.start < 0), state.time, cl.start),
        wait_ticks=cl.wait_ticks + (waiting & ~admit).astype(i32),
    )
    instances = inst._replace(n_exec=inst.n_exec + admit_per_inst)
    return state._replace(rr=rr, cloudlets=cloudlets, instances=instances)


# ===========================================================================
# Execute: time-shared progress, finish detection, usage history
# ===========================================================================

class FinishOut(NamedTuple):
    """Outputs of :func:`cloudlet_finish`."""

    new_rem: jnp.ndarray    # [C] f32
    fin: jnp.ndarray        # [C] bool
    tfin: jnp.ndarray       # [C] f32 sub-tick finish timestamp
    consumed: jnp.ndarray   # [C] f32 MI consumed this tick
    inst_acc: jnp.ndarray   # [I+1, 5] f32: used MI/s, finish count,
    #                         sojourn / exec / wait sums (row I = overflow)
    req_finish: jnp.ndarray  # [R] f32 updated max finish time per request
    req_crit: jnp.ndarray    # [R] i32 updated max critical depth
    req_out: jnp.ndarray     # [R] i32 updated outstanding count


def cloudlet_finish(status, rem, inst, req, arrival, start, depth,
                    rate, time, dt, req_finish, req_crit, req_out,
                    n_inst: int) -> FinishOut:
    """One pass over the cloudlet axis: progress, sub-tick finishes, and
    every per-finish aggregate the scheduler needs (paper §4.2).

    Per-instance statistics (usage, finish count, sojourn/exec/wait sums)
    land in ONE stacked [I+1, 5] scatter; per-service stats are derived by
    the caller over the (tiny) instance→service map; the per-request
    aggregates (max finish time, max critical depth, outstanding) are
    updated in place so the request pool is never re-streamed.

    Inputs (all [C] unless noted): status i32 (2 = executing), rem f32
    (MI), inst i32, req i32, arrival f32, start f32, depth i32, rate f32
    (MI/s), time scalar, dt scalar, req_finish/req_crit/req_out [R];
    n_inst static.
    """
    f32, i32 = jnp.float32, jnp.int32
    n_req = req_finish.shape[0]
    execm = status == CL_EXEC
    prog = rate * dt
    fin = execm & (rem <= prog) & (rate > 0)
    tfin = jnp.where(
        fin, jnp.clip(time + rem / jnp.maximum(rate, 1e-9), time, time + dt),
        0.0)
    consumed = jnp.where(execm, jnp.minimum(prog, rem), 0.0)
    new_rem = jnp.where(execm, jnp.maximum(rem - prog, 0.0), rem)
    finf = fin.astype(f32)

    # per-instance: usage + finish count + finish-time statistics in ONE
    # stacked scatter, so the cloudlet axis is streamed exactly once for
    # all five statistics
    started = jnp.maximum(start, arrival)
    sojourn = jnp.where(fin, tfin - arrival, 0.0)
    exec_t = jnp.where(fin, tfin - started, 0.0)
    wait_t = jnp.where(fin, started - arrival, 0.0)
    iidx = jnp.where(execm & (inst >= 0), inst, n_inst)
    with collide("inst_acc"):
        inst_acc = jnp.zeros((n_inst + 1, 5), f32).at[iidx].add(
            jnp.stack([consumed / dt, finf, sojourn, exec_t, wait_t], axis=1),
            mode="drop")

    # per-request finish aggregates.  Two static strategies, same results:
    #  * small request pool (R ≤ C, Table 2 services-dominated cases):
    #    stack both maxima into one pool-sized scatter, then merge — max
    #    is associative so the merge is exact, and the merge passes are
    #    over the small R;
    #  * large request pool (R > C, requests-dominated cases): update in
    #    place, so the [R] arrays are never re-streamed.
    ridx = jnp.where(fin & (req >= 0), req, n_req)
    with collide("req_finish_acc"):
        if n_req <= status.shape[0]:
            critf = jnp.where(fin, (depth + 1).astype(f32), 0.0)
            mx = jnp.zeros((n_req + 1, 2), f32).at[ridx].max(
                jnp.stack([tfin, critf], axis=1), mode="drop")
            req_finish = jnp.maximum(req_finish, mx[:n_req, 0])
            req_crit = jnp.maximum(req_crit, mx[:n_req, 1].astype(i32))
        else:
            req_finish = req_finish.at[ridx].max(tfin, mode="drop")
            req_crit = req_crit.at[ridx].max(depth + 1, mode="drop")
        req_out = req_out.at[ridx].add(-fin.astype(i32), mode="drop")

    return FinishOut(new_rem=new_rem, fin=fin, tfin=tfin, consumed=consumed,
                     inst_acc=inst_acc, req_finish=req_finish,
                     req_crit=req_crit, req_out=req_out)


def cloudlet_finish_pool(cl, rate, time, dt, req_finish, req_crit, req_out,
                         n_inst: int) -> FinishOut:
    """:func:`cloudlet_finish` over the stacked cloudlet pool: its input
    columns are sliced out of the ``[C, NI]``/``[C, NF]`` blocks through
    the mode-keyed :class:`core.types.PoolLayout` carried by ``cl`` — no
    hard-coded column positions.  Every mode registers the Execute-phase
    columns."""
    L = cl.layout
    ints, flts = cl.ints, cl.flts
    return cloudlet_finish(
        ints[:, L.i("status")], flts[:, L.f("rem")], ints[:, L.i("inst")],
        ints[:, L.i("req")], flts[:, L.f("arrival")],
        flts[:, L.f("start")], ints[:, L.i("depth")], rate, time, dt,
        req_finish, req_crit, req_out, n_inst=n_inst)


class FinishInfo(NamedTuple):
    fin: jnp.ndarray       # [C] bool finished this tick
    tfin: jnp.ndarray      # [C] f32 sub-tick finish timestamp
    pre_service: jnp.ndarray  # [C] i32 service ids before slot clearing
    pre_req: jnp.ndarray
    pre_depth: jnp.ndarray
    pre_inst: jnp.ndarray


def execute(state: SimState, app: AppStatic, caps: SimCaps,
            params: SimParams, dyn: DynParams
            ) -> Tuple[SimState, FinishInfo]:
    cl, inst, vms = state.cloudlets, state.instances, state.vms
    I = inst.status.shape[0]
    S = app.n_services
    i32, f32 = jnp.int32, jnp.float32
    dt = dyn.dt

    status_c, rem_c, inst_c = cl.status, cl.rem, cl.inst
    execm = status_c == CL_EXEC

    # n_exec is maintained incrementally (dispatch adds admissions, the
    # finish counts below subtract) — no per-tick re-count over the pool.
    n_exec = inst.n_exec
    inst_safe = jnp.where(execm, inst_c, 0)
    # Hardware heterogeneity: instances run at their host's CPU speed
    # (hosts.cpu_scale, 1.0 everywhere by default — an exact multiply);
    # the scheduler/placement still accounts the full allocation.
    mips_eff = inst.mips * state.hosts.cpu_scale[jnp.maximum(inst.host, 0)]
    if params.faults == "chaos":
        # fail-slow hosts (§7.1): a slow host's instances run at a fraction
        # of their allocation — the scheduling weights are untouched, only
        # the effective rate degrades (allocation-based util still reads
        # against inst.mips, so a slow host shows depressed utilization)
        hs = jnp.maximum(inst.host, 0)
        is_slow = (inst.host >= 0) & (state.fault.host_slow[hs] > 0)
        mips_eff = jnp.where(is_slow, mips_eff * dyn.host_slow_factor,
                             mips_eff)
    # rounded as IEEE divides on every platform: the finish test below
    # compares rem with rate * dt exactly (generator.divide_rn)
    if params.share_policy == policies.SHARE_SRPT:
        w = jnp.where(execm, 1.0 / (rem_c + 1.0), 0.0)
        wsum = _segsum(w, jnp.where(execm, inst_c, -1), I)
        rate = divide_rn(mips_eff[inst_safe] * w,
                         jnp.maximum(wsum[inst_safe], 1e-9))
    else:  # equal time slice: one quotient per instance, MIPS over n_exec
        rate = divide_rn(mips_eff, jnp.maximum(n_exec.astype(f32),
                                               1e-9))[inst_safe]
    rate = jnp.where(execm, rate, 0.0)  # MI/s

    # --- fused finish reduction: progress + every per-finish aggregate;
    # the per-request arrays are updated in place, so the (often much
    # larger) request pool is never re-streamed here ---
    req = state.requests
    out = cloudlet_finish_pool(
        cl, rate, state.time, dt,
        req.finish, req.critical_len, req.outstanding, n_inst=I)
    fin, tfin = out.fin, out.tfin
    used_mips = out.inst_acc[:I, 0]
    fin_per_inst = out.inst_acc[:I, 1].astype(i32)

    svc_of_inst = inst.service
    util = jnp.where(inst.mips > 0, used_mips / jnp.maximum(inst.mips, 1e-9),
                     0.0)
    # Usage accounting (paper §5.2): idle floor on every ON instance plus a
    # resize surcharge on vertically-scaled instances.  The scaling signal
    # (util EMA) stays based on raw consumption.
    on = inst.status == INST_ON
    acct_mips = (used_mips * (1.0 + jnp.where(
        inst.mips > inst.request_mips, dyn.vs_overhead_frac, 0.0))
        + dyn.idle_mips_frac * jnp.where(on, inst.mips, 0.0))
    a = dyn.util_ema
    util_ema = jnp.where(inst.status != INST_FREE,
                         a * util + (1 - a) * inst.util_ema, 0.0)
    used_ram = jnp.where(svc_of_inst >= 0,
                         app.ram_per_cl[jnp.maximum(svc_of_inst, 0)]
                         * n_exec.astype(f32), 0.0)

    # --- per-service usage history / node-delay estimates ---------------
    # The cloudlet-axis statistics were accumulated per instance by the
    # fused op; fold them (plus usage) into services with ONE stacked
    # scatter over the small instance table.
    st = state.svc_stats
    svc_rows = jnp.concatenate(
        [(acct_mips * dt)[:, None], out.inst_acc[:I, 1:5]], axis=1)
    sidx = jnp.where(svc_of_inst >= 0, svc_of_inst, S)
    with collide("svc_acc"):
        svc_acc = jnp.zeros((S + 1, 5), f32).at[sidx].add(
            jnp.where((svc_of_inst >= 0)[:, None], svc_rows, 0.0),
            mode="drop")
    svc_stats = st._replace(
        usage_sum=st.usage_sum + svc_acc[:S, 0],
        finished=st.finished + svc_acc[:S, 1].astype(i32),
        delay_sum=st.delay_sum + svc_acc[:S, 2],
        exec_sum=st.exec_sum + svc_acc[:S, 3],
        wait_sum=st.wait_sum + svc_acc[:S, 4],
    )

    # --- request aggregates (already folded in by the fused op) ----------
    requests = req._replace(outstanding=out.req_out, finish=out.req_finish,
                            critical_len=out.req_crit)

    info = FinishInfo(fin=fin, tfin=tfin, pre_service=cl.service,
                      pre_req=cl.req, pre_depth=cl.depth, pre_inst=inst_c)

    # --- clear finished slots (the "finished queue" is the aggregates) --
    cloudlets = cl.with_cols(
        status=jnp.where(fin, CL_FREE, status_c),
        rem=out.new_rem,
        inst=jnp.where(fin, -1, inst_c),
    )

    # --- drained instances release their VM share (HS scale-in) ---------
    n_exec_after = n_exec - fin_per_inst
    drain_done = (inst.status == INST_DRAIN) & (n_exec_after == 0)
    V = vms.mips.shape[0]
    rel_mips = _segsum(jnp.where(drain_done, inst.mips, 0.0), inst.vm, V)
    rel_ram = _segsum(jnp.where(drain_done, inst.ram, 0.0), inst.vm, V)
    vms = vms._replace(mips_used=vms.mips_used - rel_mips,
                       ram_used=vms.ram_used - rel_ram)

    instances = inst._replace(
        status=jnp.where(drain_done, INST_FREE, inst.status),
        service=jnp.where(drain_done, -1, inst.service),
        vm=jnp.where(drain_done, -1, inst.vm),
        host=jnp.where(drain_done, -1, inst.host),
        mips=jnp.where(drain_done, 0.0, inst.mips),
        ram=jnp.where(drain_done, 0.0, inst.ram),
        n_exec=n_exec_after,
        used_mips=used_mips,
        used_ram=used_ram,
        util_ema=jnp.where(drain_done, 0.0, util_ema),
        usage_sum=inst.usage_sum + acct_mips * dt,
        busy_ticks=inst.busy_ticks + (n_exec > 0).astype(i32),
    )

    counters = state.counters._replace(
        finished=state.counters.finished + jnp.sum(fin.astype(i32)))

    # --- per-edge / per-replica success counts (resilience §7, chaos mode
    # only): the next Disruption pass folds them into the breaker and
    # outlier-ejection error/latency EMAs --------------------------------
    fault = state.fault
    if params.faults == "chaos":
        E = fault.edge_succ.shape[0]
        fault = fault._replace(
            edge_succ=fault.edge_succ + _segsum(
                fin.astype(i32), jnp.where(fin, cl.edge, -1), E),
            inst_succ=fault.inst_succ + fin_per_inst,
            inst_lat_sum=fault.inst_lat_sum + out.inst_acc[:I, 2])

    return state._replace(cloudlets=cloudlets, instances=instances, vms=vms,
                          requests=requests, svc_stats=svc_stats,
                          counters=counters, fault=fault), info


# ===========================================================================
# Derive: finished cloudlets spawn successors (paper §4.1.2 "Derivative")
# ===========================================================================

def derive(state: SimState, app: AppStatic, caps: SimCaps,
           info: FinishInfo, rng: jnp.ndarray,
           params: SimParams | None = None, net_rng=None) -> SimState:
    cl, req, ctr = state.cloudlets, state.requests, state.counters
    C = cl.status.shape[0]
    R = req.api.shape[0]
    I = state.instances.status.shape[0]
    D = app.succ.shape[1]
    i32, f32 = jnp.int32, jnp.float32

    # maximum() is a no-op (fin ⇒ the slot held a real service id) but
    # pins parent_svc ∈ [0, S-1] for the succ-table row gather below
    parent_svc = jnp.where(info.fin, jnp.maximum(info.pre_service, 0), 0)
    child = app.succ[parent_svc]                      # [C, D]
    valid = info.fin[:, None] & (child >= 0)
    if app.route_p is not None:  # static: per-API trees or p < 1 calls
        with jax.named_scope("route"):
            valid, taken, skipped = _route(valid, app, req.api, info,
                                           parent_svc, rng)
        ctr = ctr._replace(branch_taken=ctr.branch_taken + taken,
                           branch_skipped=ctr.branch_skipped + skipped)
    valid = valid.reshape(-1)
    svc_flat = child.reshape(-1)
    req_flat = jnp.broadcast_to(info.pre_req[:, None], (C, D)).reshape(-1)
    dep_flat = jnp.broadcast_to((info.pre_depth + 1)[:, None],
                                (C, D)).reshape(-1)
    tf_flat = jnp.broadcast_to(info.tfin[:, None], (C, D)).reshape(-1)
    pin_flat = jnp.broadcast_to(info.pre_inst[:, None], (C, D)).reshape(-1)

    asg = assign_free_slots(cl.status == CL_FREE, valid, k_static=C)
    Ka = asg.dst.shape[0]
    svc_new = svc_flat[asg.src]          # rank-level gathers
    req_new = req_flat[asg.src]
    # clamp is a no-op (build validation rejects call-graph cycles, so a
    # parent at depth S-1 has exhausted every service and can have no
    # successors) but keeps the depth column inside its declared
    # [0, S-1] bound
    dep_new = jnp.minimum(dep_flat[asg.src], app.succ.shape[0] - 1)
    tf_new = tf_flat[asg.src]
    # Edge id: row = parent service, column = successor slot (§7).
    psvc_new = jnp.broadcast_to(parent_svc[:, None],
                                (C, D)).reshape(-1)[asg.src]
    slot_new = (asg.src % D).astype(i32)
    edge_new = psvc_new * D + slot_new
    pin_new = pin_flat[asg.src]
    noise = jax.random.normal(rng, (Ka,), f32)
    length = jnp.maximum(app.len_mean[svc_new] + app.len_std[svc_new] * noise,
                         1.0)

    if net_rng is None:                  # uniform mode (degenerate network)
        status_new, inst_new = CL_WAITING, -1
        src_host_new, bytes_new = -1, 0.0
        rr = state.rr
    else:                                # fabric mode: address + payload
        k_lb, k_pay = streams.split(net_rng, names=("lb", "payload"))
        tgt, rr = netmod.pick_replicas(svc_new, asg.live, state, caps,
                                       params, k_lb)
        payload = netmod.sample_payload(app.payload_mean[psvc_new, slot_new],
                                        app.payload_std[psvc_new, slot_new],
                                        k_pay)
        src_host = jnp.where(pin_new >= 0,
                             state.instances.host[jnp.maximum(pin_new, 0)],
                             -1)
        dst_host = jnp.where(tgt >= 0,
                             state.instances.host[jnp.maximum(tgt, 0)], -1)
        # Loopback fast path: co-located hops never touch a NIC — they
        # land directly in the waiting queue at the parent's finish time.
        loop = (tgt >= 0) & (src_host >= 0) & (src_host == dst_host)
        in_transit = (tgt >= 0) & ~loop
        status_new = jnp.where(in_transit, CL_TRANSIT, CL_WAITING)
        inst_new = tgt
        src_host_new = jnp.where(in_transit, src_host, -1)
        bytes_new = jnp.where(in_transit, payload, 0.0)

    # Fused spawn write: two scatters for the whole successor wave.
    cloudlets = scatter_pool(
        cl, asg,
        status=status_new, req=req_new, service=svc_new, inst=inst_new,
        wait_ticks=0, depth=dep_new, src_host=src_host_new,
        attempt=0, edge=edge_new, src_inst=pin_new,
        length=length, rem=length, arrival=tf_new, start=-1.0,
        rem_bytes=bytes_new)

    # several successors of one parent share a request — intended collisions
    rdst = jnp.where(asg.live, req_new, R)
    with collide("spawn_request_counts"):
        requests = req._replace(
            outstanding=req.outstanding.at[rdst].add(1, mode="drop"),
            spawned=req.spawned.at[rdst].add(1, mode="drop"))

    # Outbound-RPC bandwidth (linear usage model, paper §5.2).
    live_pinst = jnp.where(asg.live, pin_flat[asg.src], -1)
    psvc = jnp.where(asg.live, jnp.maximum(
        state.instances.service[jnp.maximum(live_pinst, 0)], 0), 0)
    bw = _segsum(app.bytes_per_rpc[psvc] * asg.live.astype(f32),
                 live_pinst, I)
    instances = state.instances._replace(used_bw=bw)

    counters = ctr._replace(
        spawned=ctr.spawned + asg.n_assigned,
        dropped_cloudlets=ctr.dropped_cloudlets + asg.n_dropped)
    return state._replace(rr=rr, cloudlets=cloudlets, requests=requests,
                          instances=instances, counters=counters)


def _route(valid: jnp.ndarray, app: AppStatic, req_api: jnp.ndarray,
           info: FinishInfo, parent_svc: jnp.ndarray, rng: jnp.ndarray):
    """The calls of ``valid`` [C, D] that each finished call's request
    makes: those its API takes from the parent's service, a conditional
    one (0 < p < 1) where a uniform draw falls under p.  The draws come
    from their own stream off the Derive key, so no other draw moves.
    Returns (calls made, conditional calls made, conditional calls not
    made)."""
    api = jnp.where(info.fin, req_api[jnp.maximum(info.pre_req, 0)], 0)
    p = app.route_p[jnp.maximum(api, 0), parent_svc]          # [C, D]
    u = jax.random.uniform(streams.fold_in(rng, 1, name="route"), p.shape)
    made = u < p
    drawn = valid & (p > 0.0) & (p < 1.0)
    i32 = jnp.int32
    return (valid & made, jnp.sum((drawn & made).astype(i32)),
            jnp.sum((drawn & ~made).astype(i32)))


# ===========================================================================
# Complete: close requests whose dependency tree drained (paper §4.3.2)
# ===========================================================================

def complete(state: SimState, dyn: DynParams, faults: bool = False
             ) -> Tuple[SimState, jnp.ndarray]:
    req, ctr = state.requests, state.counters
    i32 = jnp.int32
    done = ((req.outstanding == 0) & (req.spawned > 0) & (req.response < 0)
            & (req.arrival >= 0))
    resp = jnp.where(done, req.finish - req.arrival, req.response)
    n_done = jnp.sum(done.astype(i32))
    viol = done & (resp * 1000.0 > dyn.slo_ms)
    if faults:
        # a failed completion is an SLO violation regardless of how fast
        # it failed — else breaker fail-fasts would IMPROVE the SLO rate
        viol = viol | (done & (req.failed > 0))
    counters = ctr._replace(
        completed=ctr.completed + n_done,
        resp_sum=ctr.resp_sum + jnp.sum(jnp.where(done, resp, 0.0)),
        slo_violations=ctr.slo_violations + jnp.sum(viol.astype(i32)),
    )
    state = state._replace(requests=req._replace(response=resp),
                           counters=counters)
    if faults:
        # a request whose failed flag is set completes as a FAILED
        # completion — counted exactly once, at its single `done` tick
        n_fail = jnp.sum((done & (req.failed > 0)).astype(i32))
        state = state._replace(fstats=state.fstats._replace(
            failed_requests=state.fstats.failed_requests + n_fail))
    return state, n_done

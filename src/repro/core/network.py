"""Network fabric: host NICs, payload transit, fair-share contention.

The paper's central challenge is "frequent inter-service communication",
but its transport model (and this repo's ``network="uniform"`` degenerate
mode) is a single load-independent latency per RPC hop.  The fabric mode
(DESIGN.md §6) makes the network a first-class tick phase:

* every instance is attached to a host NIC (``Instances.host``, co-located
  with its VM);
* every RPC carries a Gaussian payload sampled from the service edge it
  traverses (``AppStatic.payload_mean/std``) and is *addressed* to a
  replica at spawn time (client-side load balancing — the transfer needs a
  destination NIC before it can contend);
* in-flight transfers sit in the stacked cloudlet pool under ``CL_TRANSIT``
  with ``rem_bytes`` / ``src_host`` columns, and each tick the max-min fair
  water-filling (:func:`link_share`) splits every egress and ingress port
  among its transfers before ``dispatch`` admits the arrivals;
* intra-host hops take a loopback fast path (spawned directly into the
  waiting queue — no NIC occupancy, no transit tick).

The phase streams the cloudlet buffer a constant number of times and keeps
all statistics in small host-table scatters, preserving the one-pass tick
discipline of DESIGN.md §2.2.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import policies
from .app import AppStatic
from .pool import segment_rank, segment_sum as _segsum
from .types import (CL_TRANSIT, CL_WAITING, DynParams, INST_ON, SimCaps,
                    SimParams, SimState)

# Payload floor (MB): Gaussian sampling may go non-positive; a transfer must
# carry at least one packet so it arrives in finite time.
MIN_PAYLOAD_MB = 1e-6

# NIC capacities are configured in Mbit/s; transfers account in MByte.
MBIT_PER_S_TO_MBYTE_PER_S = 1.0 / 8.0

# One-hot accounting matrices ([C, H] / [C, NB]) beat serialized scatters
# on CPU/TPU only while they fit comfortably in cache; past this element
# budget the O(C) scatter takes over.  Counts are small integers (exact in
# f32 in any order), so the switch is value-safe.
ONE_HOT_BUDGET = 1 << 22

# A port counts as saturated once its residual capacity falls below this
# relative tolerance — exact-arithmetic zero crossings land within a few ULP.
SAT_REL = 1e-5


# ===========================================================================
# Max-min fair NIC bandwidth sharing
# ===========================================================================
#
# Every in-flight transfer ``t`` occupies up to two ports: the egress NIC of
# its source host (``src[t]``, -1 = external client — no egress constraint)
# and the ingress NIC of its destination host (``dst[t]``).  The max-min fair
# allocation is computed by progressive water-filling:
#
#   repeat ``iters`` times:
#     * per-port fair share  s_p = remaining_cap_p / live_transfers_on_p
#     * global water level   λ   = min over occupied ports of s_p
#     * every live transfer gains λ; ports drain λ·n_p
#     * transfers touching a now-saturated port freeze at their current rate
#
#   finally, still-live transfers (more bottleneck levels than rounds) take
#   one conservative fill: min over their ports of the residual fair share —
#   always capacity-feasible, so the allocation never oversubscribes a link.
#
# The recurrence is exact max-min when the scenario has at most ``iters``
# distinct bottleneck water levels; beyond that it under-allocates only the
# transfers still live after the last round.

@functools.partial(jax.jit, static_argnames=("iters",))
def link_share(src, dst, active, cap_e, cap_i, iters: int = 4):
    """Max-min fair per-transfer rates (MB/s) over host NIC ports.

    Jitted, so a call outside the tick runs compiled as the Transit phase
    does: eager op-by-op execution rounds FMA-fusable chains differently
    (~1 ULP).

    Parameters (all jnp arrays)
    ---------------------------
    src : [C] i32 source host per transfer (-1 = no egress constraint).
    dst : [C] i32 destination host per transfer.
    active : [C] bool transfer is in flight.
    cap_e / cap_i : [H] f32 egress / ingress port capacities (MB/s).
    iters : static number of freeze rounds.

    Returns [C] f32 per-transfer rates (MB/s); 0 on inactive transfers.
    """
    f32 = jnp.float32
    H = cap_e.shape[0]
    inf = jnp.asarray(jnp.inf, f32)

    live = active & (dst >= 0)
    rate = jnp.zeros(src.shape, f32)
    rem_e = cap_e.astype(f32)
    rem_i = cap_i.astype(f32)

    # Port occupancy: one-hot reduction (vectorizes where CPU/TPU scatters
    # serialize) while [C, H] fits the budget, scatter-add beyond.
    hosts = jnp.arange(H, dtype=src.dtype)
    one_hot = src.shape[0] * H <= ONE_HOT_BUDGET

    def occupancy(live):
        has_src = live & (src >= 0)
        if one_hot:
            n_e = jnp.sum(jnp.where(has_src[:, None],
                                    src[:, None] == hosts[None, :], False)
                          .astype(f32), axis=0)
            n_i = jnp.sum(jnp.where(live[:, None],
                                    dst[:, None] == hosts[None, :], False)
                          .astype(f32), axis=0)
        else:
            eidx = jnp.where(has_src, src, H)
            iidx = jnp.where(live, dst, H)
            n_e = jnp.zeros((H + 1,), f32).at[eidx].add(
                1.0, mode="drop")[:H]
            n_i = jnp.zeros((H + 1,), f32).at[iidx].add(
                1.0, mode="drop")[:H]
        return n_e, n_i

    for _ in range(iters):
        n_e, n_i = occupancy(live)
        share_e = rem_e / jnp.maximum(n_e, 1.0)
        share_i = rem_i / jnp.maximum(n_i, 1.0)
        lam = jnp.minimum(
            jnp.min(jnp.where(n_e > 0, share_e, inf)),
            jnp.min(jnp.where(n_i > 0, share_i, inf)))
        lam = jnp.where(jnp.isfinite(lam), jnp.maximum(lam, 0.0), 0.0)
        rate = rate + jnp.where(live, lam, 0.0)
        rem_e = rem_e - lam * n_e
        rem_i = rem_i - lam * n_i
        sat_e = (n_e > 0) & (rem_e <= SAT_REL * cap_e)
        sat_i = (n_i > 0) & (rem_i <= SAT_REL * cap_i)
        frozen = ((src >= 0) & sat_e[jnp.maximum(src, 0)]) \
            | sat_i[jnp.maximum(dst, 0)]
        live = live & ~frozen

    # Conservative final fill for transfers still live after the rounds.
    n_e, n_i = occupancy(live)
    share_e = rem_e / jnp.maximum(n_e, 1.0)
    share_i = rem_i / jnp.maximum(n_i, 1.0)
    fill = jnp.minimum(
        jnp.where(src >= 0, share_e[jnp.maximum(src, 0)], inf),
        share_i[jnp.maximum(dst, 0)])
    rate = rate + jnp.where(live, jnp.maximum(fill, 0.0), 0.0)

    # External-client uploads into an uncontended port: rate stays what the
    # water-filling gave them (ingress-limited); fully uncontended src=-1
    # transfers with dst<0 never occur (masked inactive above).
    return jnp.where(active & (dst >= 0), rate, 0.0)


def pick_replicas(svc: jnp.ndarray, live: jnp.ndarray, state: SimState,
                  caps: SimCaps, params: SimParams, rng: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Client-side load balancing at spawn time (fabric mode).

    Each new RPC in the spawn wave is addressed to a replica of its target
    service before it is sent — the transfer needs a destination NIC to
    contend on.  Uses the same policy selector as ``dispatch``; round-robin
    ranks FCFS within the wave via the prefix-sum ``segment_rank`` (no
    sort).  Returns ([K] target instance ids, -1 where no live replica
    exists; updated per-service round-robin cursors).
    """
    i32 = jnp.int32
    sched, inst = state.sched, state.instances
    S = sched.svc_replicas.shape[0]
    if params.faults == "chaos":
        # outlier ejection (§7.1): address around OPEN-ejected replicas —
        # the exact identity view when nothing is ejected
        iof, reps = policies.eject_view(sched, state.fault.inst_eject_until,
                                        state.time)
    else:
        iof, reps = sched.inst_of_rank, sched.svc_replicas
    svc_safe = jnp.where(live, svc, 0)
    replicas = reps[svc_safe]
    rep_safe = jnp.maximum(replicas, 1)

    # Shared three-policy rank selection (policies.lb_rank); round-robin
    # is offset by the FCFS rank within the spawn wave (prefix-sum
    # segment_rank — no sort), where dispatch uses slot order.
    offset = (segment_rank(svc_safe, live, S).astype(i32)
              if params.lb_policy == policies.LB_ROUND_ROBIN
              else jnp.zeros(svc.shape, i32))
    rank = policies.lb_rank(
        params.lb_policy, state.rr, svc_safe, rep_safe, offset, rng,
        iof, inst.status, inst.n_exec, inst.mips)

    target = iof[svc_safe, jnp.minimum(rank, caps.max_replicas - 1)]
    ok = live & (replicas > 0) & (target >= 0)
    tgt_safe = jnp.where(ok, target, 0)
    ok = ok & (inst.status[tgt_safe] == INST_ON)

    new_rr = state.rr
    if params.lb_policy == policies.LB_ROUND_ROBIN:
        # Advance cursors only for spawns that were actually addressed:
        # a failed address parks the cloudlet WAITING and dispatch's fresh
        # LB both serves it and steps the cursor — counting it here too
        # would double-step and skew replica fairness.
        counts = _segsum(ok.astype(i32), jnp.where(ok, svc, -1), S)
        new_rr = (state.rr + counts) % jnp.maximum(sched.svc_replicas, 1)
    return jnp.where(ok, target, -1), new_rr


def sample_payload(mean: jnp.ndarray, std: jnp.ndarray, rng: jnp.ndarray
                   ) -> jnp.ndarray:
    """Gaussian per-RPC payload (MB), floored at MIN_PAYLOAD_MB."""
    noise = jax.random.normal(rng, mean.shape, jnp.float32)
    return jnp.maximum(mean + std * noise, MIN_PAYLOAD_MB)


def inflight_mb(cl) -> jnp.ndarray:
    """Σ remaining MB of transfers on the fabric — the telemetry
    gauge behind the ``net_mb_inflight`` metric column (obs, §9)."""
    transit_m = cl.status == CL_TRANSIT
    return jnp.sum(jnp.where(transit_m, cl.rem_bytes,
                             jnp.zeros_like(cl.rem_bytes)))


def transit(state: SimState, caps: SimCaps, params: SimParams,
            dyn: DynParams, app: AppStatic | None = None) -> SimState:
    """One fabric tick: water-fill every NIC port, advance transfers,
    deliver arrivals into the waiting queue (Transit phase, DESIGN.md §6).

    NIC capacities must be positive: a zero-capacity port (swept
    ``nic_*_mbps=0`` or a zero host scale) yields zero rates, and its
    transfers legitimately never arrive — the run reports zero completions
    rather than inventing transport.

    ``app`` supplies the host→zone table for partial partitions under
    ``faults="chaos"`` (zone-pair link cuts, §7.1): a cut transfer gets
    zero capacity in the water-fill and stalls until the partition heals
    or its attempt times out — nothing crashes.
    """
    cl, inst, net = state.cloudlets, state.instances, state.net
    i32, f32 = jnp.int32, jnp.float32
    H = state.hosts.egress_scale.shape[0]
    NB = net.hist.shape[0]
    dt = dyn.dt

    active = cl.status == CL_TRANSIT
    inst_safe = jnp.maximum(cl.inst, 0)
    dst = jnp.where(active & (cl.inst >= 0), inst.host[inst_safe], -1)
    src = cl.src_host
    cap_e = (state.hosts.egress_scale * dyn.nic_egress_mbps
             * MBIT_PER_S_TO_MBYTE_PER_S)
    cap_i = (state.hosts.ingress_scale * dyn.nic_ingress_mbps
             * MBIT_PER_S_TO_MBYTE_PER_S)
    flowing = active & (dst >= 0)
    if params.faults == "chaos":
        # NIC degradation / brownout (Disruption schedule, §7): a degraded
        # host's ports run at the severity factor sampled when the episode
        # began (FaultState.nic_factor, 1.0 while healthy)
        nic = state.fault.nic_factor
        cap_e = cap_e * nic
        cap_i = cap_i * nic
        if app is not None:
            # partial partition: zero the capacity of transfers crossing a
            # cut zone pair (client ingress, src = -1, is never cut)
            hz = app.host_zone
            cut = (src >= 0) & (dst >= 0) \
                & (state.fault.zone_cut[hz[jnp.maximum(src, 0)],
                                        hz[jnp.maximum(dst, 0)]] > 0)
            flowing = flowing & ~cut

    rate = link_share(src, dst, flowing, cap_e, cap_i,
                      iters=params.waterfill_iters)

    if params.egress_shaping:
        # Per-instance egress shaping (§6 follow-up): an instance's
        # concurrent transfers share its own ``Instances.bw`` allowance on
        # top of the port-level water-fill — the clamp only ever lowers
        # rates, so NIC feasibility is preserved.  ``src_inst`` is a
        # chaos-phase column otherwise; this opt-in registers it via
        # PHASE_COLUMNS["Transit/egress_shaping"] (DESIGN.md §2.4).
        I = inst.status.shape[0]
        sin = cl.src_inst
        shaped = active & (sin >= 0)
        sin_safe = jnp.maximum(sin, 0)
        n_from = _segsum(shaped.astype(f32), jnp.where(shaped, sin, -1), I)
        share = (inst.bw[sin_safe] * MBIT_PER_S_TO_MBYTE_PER_S
                 / jnp.maximum(n_from[sin_safe], 1.0))
        rate = jnp.where(shaped, jnp.minimum(rate, share), rate)

    rem = cl.rem_bytes
    prog = rate * dt
    # Defensive: a transfer whose target instance vanished (drained between
    # spawn and now) has no NIC to arrive at — deliver it immediately and
    # let dispatch re-balance it.
    stranded = active & (dst < 0)
    arrived = (active & (rem <= prog) & (rate > 0)) | stranded
    t_arr = jnp.clip(state.time + rem / jnp.maximum(rate, 1e-9),
                     state.time, state.time + dt)
    t_arr = jnp.where(stranded, state.time, t_arr)
    moved = jnp.where(active, jnp.minimum(prog, rem), 0.0)
    new_rem = jnp.where(arrived, 0.0,
                        jnp.where(active, jnp.maximum(rem - prog, 0.0), rem))

    cloudlets = cl.with_cols(
        status=jnp.where(arrived, CL_WAITING, cl.status),
        rem_bytes=new_rem)

    # --- per-host accounting ---------------------------------------------
    # Utilization is goodput-based (bytes moved / port capacity): the
    # water-fill hands a lone transfer the whole port, so the allocated
    # rate would read as "saturated" even when only a header crossed.
    C = src.shape[0]
    if C * H <= ONE_HOT_BUDGET:     # one-hot masked sums vectorize
        hosts = jnp.arange(H, dtype=src.dtype)
        out_mb = jnp.sum(jnp.where((active & (src >= 0))[:, None]
                                   & (src[:, None] == hosts[None, :]),
                                   moved[:, None], 0.0), axis=0)
        in_mb = jnp.sum(jnp.where((active & (dst >= 0))[:, None]
                                  & (dst[:, None] == hosts[None, :]),
                                  moved[:, None], 0.0), axis=0)
    else:                           # huge pools × many hosts: O(C) scatter
        out_mb = _segsum(moved, jnp.where(active, src, -1), H)
        in_mb = _segsum(moved, jnp.where(active, dst, -1), H)
    util_e = out_mb / jnp.maximum(cap_e * dt, 1e-9)
    util_i = in_mb / jnp.maximum(cap_i * dt, 1e-9)

    # --- transit-time statistics (sub-tick arrival vs spawn time) -------
    # Stranded deliveries are excluded: their "duration" is time spent
    # addressed to a dead replica, not fabric crossing time, and would
    # pollute the percentiles during heavy scale-in churn.
    real = arrived & ~stranded
    dur = jnp.where(real, t_arr - cl.arrival, 0.0)
    bucket = jnp.clip((dur / params.net_hist_bin_s).astype(i32), 0, NB - 1)
    if C * NB <= ONE_HOT_BUDGET:
        bins = jnp.arange(NB, dtype=i32)
        hist = net.hist + jnp.sum(
            (real[:, None] & (bucket[:, None] == bins[None, :]))
            .astype(i32), axis=0)
    else:
        hist = net.hist + _segsum(
            jnp.ones((C,), i32), jnp.where(real, bucket, -1), NB)
    n_arr = jnp.sum(real.astype(i32))

    net = net._replace(
        bytes_out=net.bytes_out + out_mb,
        bytes_in=net.bytes_in + in_mb,
        egress_busy=net.egress_busy + util_e * dt,
        ingress_busy=net.ingress_busy + util_i * dt,
        transits=net.transits + n_arr,
        transit_sum=net.transit_sum + jnp.sum(dur),
        hist=hist)
    return state._replace(cloudlets=cloudlets, net=net)

"""Network fabric (DESIGN.md §6): link_share water-filling, transit
semantics, and the uniform-mode bit-identity guarantee.

Pinned contracts:

 * ``link_share`` satisfies the max-min fairness properties on hand-built
   and randomized port topologies (never oversubscribes a port, exact
   water levels on small cases);
 * ``network="uniform"`` builds the exact pre-PR program: run()/run_batch()
   responses, counters and traces are bit-identical to digests captured at
   the commit before the fabric landed;
 * fabric-mode conservation: every spawned transfer either arrives or is
   still in flight; loopback hops never touch a NIC;
 * a low-bandwidth sockshop sweep shows monotonically increasing p95
   transit time with offered load (the saturation scenario the uniform
   model cannot express).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (InstanceTemplate, SimCaps, SimParams, Simulation,
                        batch_item, diamond, summarize)
from repro.core.types import CL_TRANSIT
from repro.core.network import link_share

i32, f32 = jnp.int32, jnp.float32


# ---------------------------------------------------------------------------
# link_share: max-min fairness properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C,H,seed,iters", [
    *(pytest.param(256, 5, seed, 4, id=str(seed)) for seed in range(4)),
    # pool sizes from 8 to 1,024 transfers, one to eight freeze rounds
    (64, 4, 0, 4), (300, 7, 1, 4), (1024, 16, 2, 8), (8, 2, 3, 1),
])
def test_link_share_never_oversubscribes(C, H, seed, iters):
    r = np.random.default_rng(seed)
    src = np.asarray(r.integers(-1, H, C), np.int32)
    dst = np.asarray(r.integers(0, H, C), np.int32)
    active = r.random(C) < 0.7
    cap_e = np.asarray(r.uniform(0.5, 20.0, H), np.float32)
    cap_i = np.asarray(r.uniform(0.5, 20.0, H), np.float32)
    rate = np.asarray(link_share(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(active),
        jnp.asarray(cap_e), jnp.asarray(cap_i), iters=iters))
    assert (rate >= 0).all()
    assert (rate[~active] == 0).all()
    for h in range(H):
        used_e = rate[active & (src == h)].sum()
        used_i = rate[active & (dst == h)].sum()
        assert used_e <= cap_e[h] * (1 + 1e-4), h
        assert used_i <= cap_i[h] * (1 + 1e-4), h


@pytest.mark.parametrize("C,H,seed", [(256, 5, 0), (300, 7, 3),
                                      (1024, 16, 4), (2000, 10, 6)])
def test_link_share_is_max_min_fair(C, H, seed):
    """With as many rounds as there can be water levels (one per port),
    the allocation is max-min fair: every flowing transfer crosses a
    saturated port on which no transfer gets more than it does."""
    r = np.random.default_rng(seed)
    src = np.asarray(r.integers(-1, H, C), np.int32)
    dst = np.asarray(r.integers(0, H, C), np.int32)
    active = r.random(C) < 0.7
    cap_e = np.asarray(r.uniform(0.5, 20.0, H), np.float32)
    cap_i = np.asarray(r.uniform(0.5, 20.0, H), np.float32)
    rate = np.asarray(link_share(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(active),
        jnp.asarray(cap_e), jnp.asarray(cap_i), iters=2 * H))
    tol = 1e-4
    on_e = [active & (src == h) for h in range(H)]
    on_i = [active & (dst == h) for h in range(H)]
    sat_e = [rate[m].sum() >= cap_e[h] * (1 - tol) for h, m in enumerate(on_e)]
    sat_i = [rate[m].sum() >= cap_i[h] * (1 - tol) for h, m in enumerate(on_i)]
    for t in np.flatnonzero(active):
        top = rate[t] * (1 + tol)
        by_egress = src[t] >= 0 and sat_e[src[t]] \
            and rate[on_e[src[t]]].max() <= top
        by_ingress = sat_i[dst[t]] and rate[on_i[dst[t]]].max() <= top
        assert by_egress or by_ingress, t


def test_link_share_exact_waterfill_small():
    """Hand-checked two-level max-min case.

    Host 0 egress cap 10 carries transfers A, B; B also rides into host 1
    whose ingress cap is 2.  Max-min: B is bottlenecked at 2 (level 1),
    then A takes the residual 8 (level 2).
    """
    src = jnp.asarray([0, 0], i32)
    dst = jnp.asarray([2, 1], i32)
    active = jnp.asarray([True, True])
    cap_e = jnp.asarray([10.0, 100.0, 100.0], f32)
    cap_i = jnp.asarray([100.0, 2.0, 100.0], f32)
    rate = np.asarray(link_share(src, dst, active, cap_e, cap_i, iters=4))
    np.testing.assert_allclose(rate, [8.0, 2.0], rtol=1e-5)


def test_link_share_client_uploads_share_ingress():
    """Three client (src=-1) transfers into one host split its ingress
    evenly — no egress constraint applies."""
    src = jnp.asarray([-1, -1, -1], i32)
    dst = jnp.asarray([0, 0, 0], i32)
    active = jnp.ones(3, bool)
    cap_e = jnp.asarray([5.0], f32)
    cap_i = jnp.asarray([9.0], f32)
    rate = np.asarray(link_share(src, dst, active, cap_e, cap_i, iters=4))
    np.testing.assert_allclose(rate, [3.0, 3.0, 3.0], rtol=1e-5)


def test_link_share_many_levels_is_conservative():
    """More bottleneck levels than freeze rounds: the allocation must stay
    feasible (the final fill never oversubscribes)."""
    H = 8
    # one transfer per (host h egress → host h+1 ingress), capacities
    # descending so every round freezes exactly one level
    src = jnp.asarray(list(range(H - 1)), i32)
    dst = jnp.asarray(list(range(1, H)), i32)
    active = jnp.ones(H - 1, bool)
    cap = np.linspace(1.0, 10.0, H).astype(np.float32)
    rate = np.asarray(link_share(src, dst, active, jnp.asarray(cap),
                                 jnp.asarray(cap), iters=2))
    for h in range(H - 1):
        assert rate[h] <= cap[h] * (1 + 1e-4)


# ---------------------------------------------------------------------------
# uniform degenerate mode: bit-identical to the pre-PR engine
# ---------------------------------------------------------------------------

def _digest_f32(x) -> int:
    a = np.ascontiguousarray(np.asarray(x, np.float32))
    return int(a.view(np.uint32).astype(np.uint64).sum())


def _digest_i32(x) -> int:
    a = np.ascontiguousarray(np.asarray(x, np.int32))
    return int(a.astype(np.int64).sum())


def _diamond_sim():
    caps = SimCaps(n_clients=16, max_requests=512, max_cloudlets=512,
                   max_instances=8, n_vms=2, d_max=2, max_replicas=2)
    params = SimParams(dt=0.05, n_ticks=300, n_clients=12, spawn_rate=5.0,
                       wait_lo=0.5, wait_hi=1.5, scaling_policy=1,
                       scale_interval=40, net_latency_s=0.05, seed=3)
    return Simulation(diamond(mi=400.0), caps=caps, params=params), params


# Digests captured at commit db98924 (the commit before the network fabric),
# by running these exact scenarios and summing the bit patterns of the
# outputs — see the capture script quoted in the PR description.
GOLDEN = dict(
    diamond_resp=1610947120196,
    diamond_completed=16,
    diamond_spawned=240,
    diamond_trace_completed=16,
    diamond_trace_used_mips=348533711833,
    diamond_scale_out=5,
    batch_resp=(1621571898612, 1610947120196, 1625837432215),
)


def test_uniform_mode_bit_identical_to_pre_fabric_run():
    sim, _ = _diamond_sim()
    res = sim.run()
    st = res.state
    assert _digest_f32(st.requests.response) == GOLDEN["diamond_resp"]
    assert int(st.counters.completed) == GOLDEN["diamond_completed"]
    assert int(st.counters.spawned) == GOLDEN["diamond_spawned"]
    assert _digest_i32(res.trace.completed) == \
        GOLDEN["diamond_trace_completed"]
    assert _digest_f32(res.trace.used_mips) == \
        GOLDEN["diamond_trace_used_mips"]
    assert int(st.counters.scale_out) == GOLDEN["diamond_scale_out"]
    # the fabric state exists but never moves in uniform mode
    assert int(st.net.transits) == 0
    assert float(np.asarray(st.net.bytes_in).sum()) == 0.0
    assert int(np.asarray(res.trace.n_transit).sum()) == 0


def test_uniform_mode_bit_identical_run_batch():
    sim, params = _diamond_sim()
    sweeps = [dataclasses.replace(params, n_clients=nc)
              for nc in (6, 12, 16)]
    res_b = sim.run_batch(sweeps)
    for b, want in enumerate(GOLDEN["batch_resp"]):
        item = batch_item(res_b, b)
        assert _digest_f32(item.state.requests.response) == want, b


# ---------------------------------------------------------------------------
# fabric-mode engine semantics
# ---------------------------------------------------------------------------

def _fabric_sim(mbps: float, n_ticks: int = 300, seed: int = 3,
                n_clients: int = 12):
    caps = SimCaps(n_clients=16, max_requests=512, max_cloudlets=512,
                   max_instances=8, n_vms=2, d_max=2, max_replicas=2)
    params = SimParams(dt=0.05, n_ticks=n_ticks, n_clients=n_clients,
                       spawn_rate=5.0, wait_lo=0.5, wait_hi=1.5, seed=seed,
                       network="fabric", nic_egress_mbps=mbps,
                       nic_ingress_mbps=mbps)
    tmpl = InstanceTemplate(mips=8000.0, limit_mips=16000.0)
    vm_mips = np.full(2, 64000.0, np.float32)
    return Simulation(diamond(mi=400.0), caps=caps, params=params,
                      default_template=tmpl, vm_mips=vm_mips)


def test_fabric_transfer_conservation():
    sim = _fabric_sim(50.0)
    res = sim.run()
    st = res.state
    in_flight = int(np.asarray(
        (st.cloudlets.status == CL_TRANSIT)).sum())
    # histogram counts exactly the arrived transfers
    assert int(np.asarray(st.net.hist).sum()) == int(st.net.transits)
    assert int(st.net.transits) > 0
    # bytes only move through the fabric while transfers are in flight
    assert float(np.asarray(st.net.bytes_in).sum()) > 0
    # requests complete despite transit (the phase delivers)
    assert int(st.counters.completed) > 0
    # in-flight leftovers are bounded by the pool
    assert 0 <= in_flight <= st.cloudlets.status.shape[0]


def test_fabric_loopback_beats_cross_host():
    """All instances on one VM → every hop is loopback: no NIC bytes, no
    transits except the client→entry uploads."""
    caps = SimCaps(n_clients=8, max_requests=256, max_cloudlets=256,
                   max_instances=8, n_vms=1, d_max=2, max_replicas=2)
    params = SimParams(dt=0.05, n_ticks=200, n_clients=6, spawn_rate=5.0,
                       wait_lo=0.5, wait_hi=1.5, seed=0,
                       network="fabric", nic_egress_mbps=100.0,
                       nic_ingress_mbps=100.0)
    sim = Simulation(diamond(mi=200.0), caps=caps, params=params,
                     default_template=InstanceTemplate(mips=8000.0,
                                                       limit_mips=16000.0),
                     vm_mips=np.full(1, 64000.0, np.float32))
    res = sim.run()
    st = res.state
    assert int(st.counters.completed) > 0
    # every fabric transfer is a client upload: zero egress anywhere
    assert float(np.asarray(st.net.bytes_out).sum()) == 0.0
    # client uploads did arrive through the ingress port
    assert float(np.asarray(st.net.bytes_in).sum()) > 0.0
    # derived hops took the loopback fast path: transits == root arrivals
    assert int(st.net.transits) <= int(st.requests.count) + 1


def test_fabric_low_bandwidth_increases_transit_p95():
    reps = {}
    for mbps in (100.0, 2.0):
        sim = _fabric_sim(mbps)
        reps[mbps] = summarize(sim, sim.run())
    assert reps[2.0].transit_p95_ms > reps[100.0].transit_p95_ms
    assert reps[2.0].avg_ingress_util > reps[100.0].avg_ingress_util


def test_fabric_saturation_p95_monotone_with_load():
    """Acceptance scenario: low-bandwidth sockshop sweep — p95 transit time
    rises monotonically with offered load (a saturation curve the uniform
    latency model cannot produce).  Spread placement puts services on
    different hosts so RPC edges actually cross NICs."""
    from repro.configs.sockshop import make_sim
    from repro.core import policies
    sim = make_sim(n_clients=96, duration_s=40.0, seed=0,
                   network="fabric", nic_egress_mbps=8.0,
                   nic_ingress_mbps=8.0,
                   placement_policy=policies.PLACE_SPREAD)
    base = sim.params
    sweeps = [dataclasses.replace(base, n_clients=nc, spawn_rate=nc / 10.0)
              for nc in (8, 32, 96)]
    res_b = sim.run_batch(sweeps)
    p95 = []
    for b, p in enumerate(sweeps):
        rep = summarize(sim, batch_item(res_b, b), params=p)
        p95.append(rep.transit_p95_ms)
    assert all(b >= a for a, b in zip(p95, p95[1:])), p95
    assert p95[-1] > p95[0], p95


def test_fabric_nic_bandwidth_sweepable_via_dynparams():
    """run_batch sweeps NIC capacity without recompiling; each point
    matches its solo run bit for bit."""
    sim = _fabric_sim(100.0)
    base = sim.params
    sweeps = [dataclasses.replace(base, nic_egress_mbps=m,
                                  nic_ingress_mbps=m)
              for m in (100.0, 4.0)]
    res_b = sim.run_batch(sweeps)
    for b, p in enumerate(sweeps):
        caps = sim.caps
        solo = Simulation(sim.graph, caps=caps, params=p,
                          default_template=InstanceTemplate(
                              mips=8000.0, limit_mips=16000.0),
                          vm_mips=np.full(2, 64000.0, np.float32)).run()
        item = batch_item(res_b, b)
        np.testing.assert_array_equal(
            np.asarray(item.state.requests.response),
            np.asarray(solo.state.requests.response))
        assert int(item.state.net.transits) == int(solo.state.net.transits)


def test_fabric_round_robin_uses_all_replicas():
    """Regression: the spawn-time cursor advance must not be repeated at
    dispatch (a double step of +2 per RPC pins a 2-replica service to one
    replica forever) — both replicas must see traffic."""
    from repro.core import linear_chain
    caps = SimCaps(n_clients=8, max_requests=512, max_cloudlets=256,
                   max_instances=8, n_vms=4, d_max=1, max_replicas=2)
    params = SimParams(dt=0.05, n_ticks=300, n_clients=8, spawn_rate=10.0,
                       wait_lo=0.3, wait_hi=0.6, seed=0,
                       network="fabric", nic_egress_mbps=1000.0,
                       nic_ingress_mbps=1000.0)
    from repro.core import policies
    sim = Simulation(linear_chain(2, mi=500.0), caps=caps, params=params,
                     default_template=InstanceTemplate(
                         mips=4000.0, limit_mips=8000.0, replicas=2),
                     vm_mips=np.full(4, 64000.0, np.float32),
                     placement_policy=policies.PLACE_SPREAD)
    res = sim.run()
    st = res.state
    busy = np.asarray(st.instances.busy_ticks)
    svc = np.asarray(st.instances.service)
    assert int(st.counters.completed) > 10
    for s in (0, 1):
        replicas_busy = busy[svc == s]
        assert len(replicas_busy) == 2
        # round-robin must spread executions over BOTH replicas
        assert (replicas_busy > 0).all(), (s, busy, svc)


def test_egress_shaping_bw_starved_instance_slows_transit():
    """PR-2 follow-up (§6): with ``egress_shaping=True`` an instance's
    concurrent transfers share its own ``Instances.bw`` allowance, so a
    bw-starved instance's transit time rises even on amply-provisioned
    NICs; shaping off (the default, PR-2 program) is unaffected."""
    def run_one(shaping: bool, bw: float):
        caps = SimCaps(n_clients=16, max_requests=512, max_cloudlets=512,
                       max_instances=8, n_vms=2, d_max=2, max_replicas=2)
        params = SimParams(dt=0.05, n_ticks=300, n_clients=12,
                           spawn_rate=5.0, wait_lo=0.5, wait_hi=1.5, seed=3,
                           network="fabric", nic_egress_mbps=1000.0,
                           nic_ingress_mbps=1000.0, egress_shaping=shaping)
        from repro.core import policies
        sim = Simulation(diamond(mi=400.0), caps=caps, params=params,
                         default_template=InstanceTemplate(
                             mips=8000.0, limit_mips=16000.0, bw=bw),
                         vm_mips=np.full(2, 64000.0, np.float32),
                         placement_policy=policies.PLACE_SPREAD)
        return summarize(sim, sim.run())

    rep_off = run_one(False, 0.5)
    rep_on = run_one(True, 0.5)
    rep_on_fat = run_one(True, 1000.0)
    assert rep_on.net_transits > 0
    # the starved instances' hops cross the fabric slower under shaping
    assert rep_on.avg_transit_ms > 2.0 * rep_off.avg_transit_ms
    # with ample instance bw the clamp never binds: same as shaping off
    assert abs(rep_on_fat.avg_transit_ms - rep_off.avg_transit_ms) < 1e-3


def test_network_param_validated():
    sim, params = _diamond_sim()
    bad = dataclasses.replace(params, network="mesh")
    with pytest.raises(ValueError, match="uniform.*fabric|fabric.*uniform"):
        Simulation(diamond(mi=400.0), caps=sim.caps, params=bad)

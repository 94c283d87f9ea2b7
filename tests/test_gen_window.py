"""Generation's windowed request writes vs the scatter form they replaced.

``scheduler.gen_spawn`` writes the requests it admits as one
read-modify-write of a contiguous W-row window at ``req.count``
(W = min(K, R)).  The reference below is the earlier form of the same
phase, which scattered Nc lanes into the [R] request arrays; the two must
agree bit for bit on every field of the state, on random states that hit
the clamped tail of the pool, a full pool, the per-tick admission budget,
multi-entry APIs and a cloudlet pool with fewer free slots than fires.
A structural check pins that no scatter into an [R] request array is
left under the Generation scope of the tick program.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import streams
from repro.analysis.annotate import collide
from repro.core import (InstanceTemplate, SimCaps, SimParams, Simulation,
                        build_graph)
from repro.core import network as netmod
from repro.core.pool import assign_free_slots, rank_select, scatter_pool
from repro.core.scheduler import GenResult, gen_spawn
from repro.core.types import (CL_FREE, CL_TRANSIT, CL_WAITING, DynParams)

i32, f32 = jnp.int32, jnp.float32


def gen_spawn_scatter(state, app, caps, fired, api, wait_proposal, rng,
                      dyn, params=None, net_rng=None):
    """Reference: Generation with the request writes as Nc-lane scatters
    into [R] (api, arrival) and Ka-lane scatter-adds (outstanding,
    spawned)."""
    req, cl, ctr = state.requests, state.cloudlets, state.counters
    R = req.api.shape[0]
    Nc = fired.shape[0]
    K = caps.k_fire if caps.k_fire > 0 else Nc
    K = min(K, Nc)
    E = app.api_entry.shape[1]

    rank = jnp.cumsum(fired.astype(i32)) - 1
    in_budget = fired & (rank < K) & (req.count + rank < dyn.num_limit)
    slot = req.count + rank
    has_slot = in_budget & (slot < R)
    n_accept = jnp.sum(has_slot.astype(i32))
    n_pool_drop = jnp.sum((in_budget & ~has_slot).astype(i32))
    new_wait = jnp.where(
        in_budget, wait_proposal,
        jnp.where(fired, 0, jnp.maximum(state.clients.wait - 1, 0)))

    dst = jnp.where(has_slot, slot, R)
    requests = req._replace(
        count=req.count + n_accept,
        api=req.api.at[dst].set(api, mode="drop"),
        arrival=req.arrival.at[dst].set(
            jnp.full((Nc,), 0.0, f32) + state.time, mode="drop"),
    )

    client_of_rank = jnp.zeros((K,), i32).at[
        jnp.where(has_slot & (rank < K), rank, K)
    ].set(jnp.arange(Nc, dtype=i32), mode="drop")
    ranks = jnp.arange(K, dtype=i32)
    r_live = ranks < n_accept
    api_r = api[client_of_rank]
    req_slot_r = req.count + ranks

    svc_d = app.api_entry[api_r]
    n_ent = app.api_n_entry[api_r]
    valid = (r_live[:, None] & (jnp.arange(E)[None, :] < n_ent[:, None])
             & (svc_d >= 0)).reshape(-1)
    svc_flat = svc_d.reshape(-1)
    req_flat = jnp.broadcast_to(req_slot_r[:, None], (K, E)).reshape(-1)

    asg = assign_free_slots(cl.status == CL_FREE, valid)
    Ka = asg.dst.shape[0]
    svc_new = svc_flat[asg.src]
    req_new = jnp.minimum(req_flat[asg.src], R - 1)
    api_flat = jnp.broadcast_to(api_r[:, None], (K, E)).reshape(-1)
    api_new = api_flat[asg.src]
    edge_new = app.n_services * app.succ.shape[1] + api_new
    noise = jax.random.normal(rng, (Ka,), f32)
    length = jnp.maximum(app.len_mean[svc_new] + app.len_std[svc_new] * noise,
                         1.0)

    if net_rng is None:
        status_new, inst_new = CL_WAITING, -1
        src_host_new, bytes_new = -1, 0.0
        rr = state.rr
    else:
        k_lb, k_pay = streams.split(net_rng, names=("lb", "payload"))
        tgt, rr = netmod.pick_replicas(svc_new, asg.live, state, caps,
                                       params, k_lb)
        payload = netmod.sample_payload(app.api_payload_mean[api_new],
                                        app.api_payload_std[api_new], k_pay)
        status_new = jnp.where(tgt >= 0, CL_TRANSIT, CL_WAITING)
        inst_new = tgt
        src_host_new = -1
        bytes_new = jnp.where(tgt >= 0, payload, 0.0)

    cloudlets = scatter_pool(
        cl, asg,
        status=status_new, req=req_new, service=svc_new, inst=inst_new,
        wait_ticks=0, depth=0, src_host=src_host_new,
        attempt=0, edge=edge_new, src_inst=-1,
        length=length, rem=length,
        arrival=jnp.full((Ka,), 0.0, f32) + state.time, start=-1.0,
        rem_bytes=bytes_new)

    rdst = jnp.where(asg.live, req_new, R)
    with collide("spawn_request_counts"):
        requests = requests._replace(
            outstanding=requests.outstanding.at[rdst].add(1, mode="drop"),
            spawned=requests.spawned.at[rdst].add(1, mode="drop"),
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


# ---------------------------------------------------------------------------
# fixtures: small simulations and seeded random states
# ---------------------------------------------------------------------------

NC, C = 12, 48


@functools.lru_cache(maxsize=None)
def _sim(E: int, k_fire: int, R: int = 40, network: str = "uniform"):
    """Three services, two APIs; with E=2 the first API enters two
    services and the second one (a mix of 2- and 1-entry requests)."""
    names = ["s0", "s1", "s2"]
    graph = build_graph(names, {}, [("a0", "s0", 0.6), ("a1", "s1", 0.4)],
                        {n: 100.0 for n in names}, {n: 10.0 for n in names},
                        d_max=1)
    caps = SimCaps(n_clients=NC, max_requests=R, max_cloudlets=C,
                   max_instances=6, n_vms=2, d_max=1, max_replicas=2,
                   k_fire=k_fire)
    params = SimParams(dt=0.1, n_ticks=4, n_clients=NC, seed=3,
                       network=network)
    entries = [["s0", "s2"], ["s1"]] if E == 2 else None
    return Simulation(graph, caps=caps, params=params, api_entries=entries,
                      default_template=InstanceTemplate(replicas=2))


def _inputs(sim, seed: int, count: int, n_free: int = C,
            num_limit: int | None = None):
    """A random state with ``req.count == count`` and ``n_free`` free
    cloudlet slots, plus random fire/API draws for one Generation."""
    rs = np.random.default_rng(seed)
    state = sim.init_state(seed)
    req, cl = state.requests, state.cloudlets
    R = req.api.shape[0]
    A = int(sim.app.api_entry.shape[0])
    # rows at and past count hold arbitrary old values: a row the window
    # reads but does not admit into must come back unchanged
    req = req._replace(
        count=jnp.asarray(count, i32),
        api=jnp.asarray(rs.integers(-1, A, R), i32),
        arrival=jnp.asarray(rs.uniform(0.0, 50.0, R), f32),
        outstanding=jnp.asarray(rs.integers(-2, 5, R), i32),
        spawned=jnp.asarray(rs.integers(0, 7, R), i32))
    status = np.full(C, CL_WAITING, np.int32)
    status[rs.choice(C, n_free, replace=False)] = CL_FREE
    cl = cl.with_cols(status=jnp.asarray(status))
    state = state._replace(
        requests=req, cloudlets=cl, time=jnp.asarray(3.7, f32),
        clients=state.clients._replace(
            wait=jnp.asarray(rs.integers(0, 4, NC), i32)))
    fired = jnp.asarray(rs.random(NC) < 0.75)
    api = jnp.asarray(rs.integers(0, A, NC), i32)
    wait_proposal = jnp.asarray(rs.integers(1, 30, NC), i32)
    dyn = DynParams.from_params(sim.params)
    if num_limit is not None:
        dyn = dyn._replace(num_limit=jnp.asarray(num_limit, i32))
    rng = jax.random.PRNGKey(seed + 1)
    net_rng = (jax.random.PRNGKey(seed + 2)
               if sim.params.network == "fabric" else None)
    return state, fired, api, wait_proposal, rng, dyn, net_rng


def _run(fn, sim, state, fired, api, wait_proposal, rng, dyn, net_rng):
    def go(state, fired, api, wait_proposal, rng, dyn, net_rng):
        return fn(state, sim.app, sim.caps, fired, api, wait_proposal, rng,
                  dyn, params=sim.params, net_rng=net_rng)
    return jax.jit(go)(state, fired, api, wait_proposal, rng, dyn, net_rng)


def _assert_bit_identical(got, want):
    g_leaves, g_def = jax.tree_util.tree_flatten_with_path(got)
    w_leaves, w_def = jax.tree_util.tree_flatten_with_path(want)
    assert g_def == w_def
    for (path, g), (_, w) in zip(g_leaves, w_leaves):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, path
        if g.dtype.kind == "f":
            g, w = g.view(f"u{g.itemsize}"), w.view(f"u{w.itemsize}")
        np.testing.assert_array_equal(g, w, err_msg=jax.tree_util.keystr(path))


def _check(sim, *args):
    got = _run(gen_spawn, sim, *args)
    want = _run(gen_spawn_scatter, sim, *args)
    _assert_bit_identical(got, want)
    return got


# ---------------------------------------------------------------------------
# equivalence
# ---------------------------------------------------------------------------

R_TEST = 40
# 0; mid-pool; within K of R (the window's start is clamped to R - W);
# a full pool, where every admission is a pool drop
COUNTS = {"zero": 0, "mid": 17, "tail": R_TEST - 5, "full": R_TEST}


@pytest.mark.parametrize("where", list(COUNTS))
@pytest.mark.parametrize("E", [1, 2])
@pytest.mark.parametrize("k_fire", [0, 5])
def test_window_matches_scatter(k_fire, E, where):
    sim = _sim(E, k_fire, R_TEST)
    for seed in (11, 12):
        state, *rest = _inputs(sim, seed, COUNTS[where])
        new_state, res = _check(sim, state, *rest)
        n = int(res.n_new_requests)
        if where == "full":
            assert n == 0
            assert int(new_state.counters.dropped_requests) > 0
        else:
            assert n > 0


@pytest.mark.parametrize("E", [1, 2])
def test_window_matches_scatter_short_cloudlet_pool(E):
    """Fewer free cloudlet slots than entry cloudlets: requests get fewer
    root cloudlets than entries (some none at all)."""
    sim = _sim(E, 0, R_TEST)
    state, *rest = _inputs(sim, 5, 9, n_free=3)
    new_state, res = _check(sim, state, *rest)
    assert int(new_state.counters.dropped_cloudlets) > 0


def test_window_matches_scatter_pool_smaller_than_budget():
    """R < K: the window is the whole pool (W = R)."""
    sim = _sim(2, 0, R=8)
    for count in (0, 3, 8):
        _check(sim, *_inputs(sim, 21 + count, count))


def test_window_matches_scatter_num_limit():
    """The generator's numLimit stops admission inside the budget."""
    sim = _sim(1, 0, R_TEST)
    _check(sim, *_inputs(sim, 31, 10, num_limit=13))


def test_window_matches_scatter_fabric():
    sim = _sim(2, 5, R_TEST, network="fabric")
    for where in ("mid", "tail"):
        _check(sim, *_inputs(sim, 41, COUNTS[where]))


def test_window_matches_scatter_vmapped():
    """run_batch's form: one batch of states with different counts."""
    sim = _sim(2, 5, R_TEST)
    a = _inputs(sim, 51, 3)
    b = _inputs(sim, 52, R_TEST - 2)
    stacked = jax.tree_util.tree_map(lambda x, y: jnp.stack([x, y]),
                                     a[:6], b[:6])

    def batched(fn):
        def one(state, fired, api, wait_proposal, rng, dyn):
            return fn(state, sim.app, sim.caps, fired, api, wait_proposal,
                      rng, dyn, params=sim.params)
        return jax.jit(jax.vmap(one))(*stacked)

    _assert_bit_identical(batched(gen_spawn), batched(gen_spawn_scatter))


@pytest.mark.parametrize("n,k,block", [(16000 // 50, 40, 128), (300, 300, 128),
                                       (256, 17, 128), (37, 37, 8),
                                       (5, 3, 128)])
def test_rank_select_matches_scatter(n, k, block):
    rs = np.random.default_rng(n + k)
    for p in (0.0, 0.05, 0.5, 1.0):
        mask = jnp.asarray(rs.random(n) < p)
        rank = jnp.cumsum(mask.astype(i32)) - 1
        want = jnp.zeros((k,), i32).at[jnp.where(mask, rank, k)].set(
            jnp.arange(n, dtype=i32), mode="drop")
        got = jax.jit(rank_select, static_argnums=(1, 2))(mask, k, block)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# structure of the lowered tick
# ---------------------------------------------------------------------------

_SCATTERS = ("scatter", "scatter-add", "scatter-max",
             "scatter-min", "scatter-mul")


def _walk(jaxpr, scope=""):
    """(primitive name, scope, eqn) for every eqn, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        esc = scope + ("/" if scope and stack else "") + stack
        yield eqn.primitive.name, esc, eqn
        for p in eqn.params.values():
            subs = p if isinstance(p, (tuple, list)) else (p,)
            for sub in subs:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner, esc)


def test_generation_writes_requests_through_one_window():
    """A case1b-shaped tick (one service, one API, a per-tick admission
    budget) with R = 101 and 24 clients, lengths no other dimension has."""
    R, K, NCLIENTS = 101, 8, 24
    graph = build_graph(["s0"], {}, [("api", "s0", 1.0)], {"s0": 50.0},
                        d_max=1)
    caps = SimCaps(n_clients=NCLIENTS, max_requests=R, max_cloudlets=32,
                   max_instances=4, n_vms=2, d_max=1, max_replicas=4,
                   k_fire=K)
    params = SimParams(dt=0.5, n_ticks=4, n_clients=NCLIENTS, spawn_rate=8.0,
                       wait_lo=1.0, wait_hi=3.0, seed=0)
    sim = Simulation(graph, caps=caps, params=params)
    state = sim.init_state()
    dyn = DynParams.from_params(sim.params)
    closed = jax.make_jaxpr(sim._tick)(state, dyn, sim.app)
    gen = [(name, eqn) for name, scope, eqn in _walk(closed.jaxpr)
           if "Generation" in scope]
    assert gen
    into_r = [name for name, eqn in gen if name in _SCATTERS
              and eqn.invars[0].aval.shape[:1] == (R,)]
    assert into_r == []
    # nor one with a lane per client (the rank-order compaction)
    per_client = [name for name, eqn in gen if name in _SCATTERS
                  and eqn.invars[1].aval.shape[:1] == (NCLIENTS,)]
    assert per_client == []
    windows = [(name, eqn) for name, eqn in gen
               if name in ("dynamic_slice", "dynamic_update_slice")
               and eqn.invars[0].aval.shape == (R,)]
    # api, arrival, outstanding and spawned: one read and one write each
    assert sorted(n for n, _ in windows) == \
        ["dynamic_slice"] * 4 + ["dynamic_update_slice"] * 4
    for name, eqn in windows:
        upd = (eqn.params["slice_sizes"] if name == "dynamic_slice"
               else eqn.invars[1].aval.shape)
        assert tuple(upd) == (K,)

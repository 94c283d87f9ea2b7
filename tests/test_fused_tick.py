"""Fused one-pass tick vs per-field seed references.

Three contracts of the single-pass tick refactor are pinned here:

 * the stacked-scatter spawn writer (pool.scatter_pool) bit-matches a
   per-field scatter reference on randomized pools,
 * the engine's single-pass finish reduction (scheduler.cloudlet_finish)
   bit-matches a per-field seed-style reference (separate _segsum per
   statistic),
 * prefix-sum segment_rank equals the retired sort-based ranking,
 * Simulation.run_batch equals N independent runs, point for point.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (InstanceTemplate, SimCaps, SimParams, Simulation,
                        batch_item, diamond, linear_chain, resolve_layout)
from repro.core.pool import (assign_free_slots, scatter_pool, segment_rank,
                             segment_rank_sorted)
from repro.core.scheduler import cloudlet_finish
from repro.core.types import Cloudlets

i32, f32 = jnp.int32, jnp.float32

# Mode-keyed layouts (DESIGN.md §2.2): the spawn writer must behave
# identically on the minimal default layout and the everything-enabled one.
LAYOUTS = {
    "minimal": resolve_layout(SimParams()),
    "full": resolve_layout(SimParams(network="fabric", faults="chaos",
                                     egress_shaping=True)),
}


# ---------------------------------------------------------------------------
# stacked-scatter spawn path vs per-field reference
# ---------------------------------------------------------------------------

def _per_field_spawn(ints, flts, asg, int_cols, flt_cols):
    """Seed-style reference: one scatter per field column."""
    C = ints.shape[0]
    K = asg.dst.shape[0]
    dst = jnp.where(asg.live, asg.dst, C)
    for j, col in enumerate(int_cols):
        v = jnp.broadcast_to(jnp.asarray(col, ints.dtype), (K,))
        ints = ints.at[dst, j].set(v, mode="drop")
    for j, col in enumerate(flt_cols):
        v = jnp.broadcast_to(jnp.asarray(col, flts.dtype), (K,))
        flts = flts.at[dst, j].set(v, mode="drop")
    return ints, flts


@pytest.mark.parametrize("lname", sorted(LAYOUTS))
@pytest.mark.parametrize("C,M,seed", [(64, 16, 0), (256, 300, 1),
                                      (1024, 512, 2), (33, 7, 3)])
def test_scatter_pool_bitmatches_per_field(C, M, seed, lname, rng):
    layout = LAYOUTS[lname]
    r = np.random.default_rng(seed)
    ints = jnp.asarray(r.integers(-1, 5, size=(C, len(layout.i_fields))),
                       i32)
    flts = jnp.asarray(r.normal(size=(C, len(layout.f_fields))), f32)
    cl = Cloudlets(ints, flts, layout)
    free = jnp.asarray(r.random(C) < 0.5)
    valid = jnp.asarray(r.random(M) < 0.7)
    asg = assign_free_slots(free, valid)
    K = asg.dst.shape[0]
    length = jnp.asarray(r.uniform(1, 100, K), f32)
    # the full vocabulary is always passed — columns outside the layout
    # must be skipped, so spawn sites stay mode-agnostic
    cols = dict(
        status=1, req=jnp.asarray(r.integers(0, 99, K), i32),
        service=jnp.asarray(r.integers(0, 9, K), i32), inst=-1,
        wait_ticks=0, depth=jnp.asarray(r.integers(0, 4, K), i32),
        src_host=jnp.asarray(r.integers(-1, 4, K), i32),
        attempt=jnp.asarray(r.integers(0, 3, K), i32),
        edge=jnp.asarray(r.integers(-1, 12, K), i32),
        src_inst=jnp.asarray(r.integers(-1, 6, K), i32),
        length=length, rem=length,
        arrival=jnp.asarray(r.uniform(0, 10, K), f32), start=-1.0,
        rem_bytes=jnp.asarray(r.uniform(0, 1, K), f32))
    int_cols = tuple(cols[n] for n in layout.i_fields)
    flt_cols = tuple(cols[n] for n in layout.f_fields)

    got = scatter_pool(cl, asg, **cols)
    wi, wf = _per_field_spawn(ints, flts, asg, int_cols, flt_cols)
    np.testing.assert_array_equal(np.asarray(got.ints), np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(got.flts), np.asarray(wf))
    assert got.layout is layout
    with pytest.raises(TypeError, match="missing"):
        scatter_pool(cl, asg, **{k: v for k, v in cols.items()
                                 if k != "rem"})
    with pytest.raises(TypeError, match="unknown"):
        scatter_pool(cl, asg, bogus_col=0, **cols)


# ---------------------------------------------------------------------------
# single-pass finish reduction vs per-field reference
# ---------------------------------------------------------------------------

def _mk_finish_pool(r, C, I, R):
    status = jnp.asarray(r.choice([0, 1, 2], size=C, p=[0.3, 0.2, 0.5]), i32)
    rem = jnp.asarray(r.uniform(0.1, 500.0, C), f32)
    inst = np.asarray(r.integers(0, I, C), np.int32)
    inst[r.random(C) < 0.05] = -1
    req = np.asarray(r.integers(0, R, C), np.int32)
    req[r.random(C) < 0.05] = -1
    arrival = jnp.asarray(r.uniform(0.0, 10.0, C), f32)
    start = np.asarray(r.uniform(0.0, 12.0, C), np.float32)
    start[r.random(C) < 0.3] = -1.0
    depth = jnp.asarray(r.integers(0, 6, C), i32)
    rate = jnp.asarray(r.uniform(0.0, 300.0, C), f32)
    req_finish = jnp.asarray(r.uniform(0.0, 12.0, R), f32)
    req_crit = jnp.asarray(r.integers(0, 4, R), i32)
    req_out = jnp.asarray(r.integers(0, 8, R), i32)
    return (status, rem, jnp.asarray(inst), jnp.asarray(req), arrival,
            jnp.asarray(start), depth, rate, req_finish, req_crit, req_out)


def _per_field_finish_reference(args, I):
    """Seed-style reference: one _segsum-style scatter per statistic."""
    (status, rem, inst, req, arrival, start, depth, rate,
     req_finish, req_crit, req_out) = args
    time, dt = 12.5, 0.25
    R = req_finish.shape[0]
    execm = status == 2
    prog = rate * dt
    fin = execm & (rem <= prog) & (rate > 0)
    tfin = jnp.where(fin, jnp.clip(time + rem / jnp.maximum(rate, 1e-9),
                                   time, time + dt), 0.0)
    consumed = jnp.where(execm, jnp.minimum(prog, rem), 0.0)
    new_rem = jnp.where(execm, jnp.maximum(rem - prog, 0.0), rem)

    def seg(data, idx, n):
        return jnp.zeros((n,), data.dtype).at[idx].add(data, mode="drop")

    iidx = jnp.where(execm & (inst >= 0), inst, I)
    started = jnp.maximum(start, arrival)
    cols = [consumed / dt, fin.astype(f32),
            jnp.where(fin, tfin - arrival, 0.0),
            jnp.where(fin, tfin - started, 0.0),
            jnp.where(fin, started - arrival, 0.0)]
    inst_acc = jnp.stack([seg(c, iidx, I + 1) for c in cols], axis=1)
    ridx = jnp.where(fin & (req >= 0), req, R)
    return (new_rem, fin, tfin, consumed, inst_acc,
            req_finish.at[ridx].max(tfin, mode="drop"),
            req_crit.at[ridx].max(depth + 1, mode="drop"),
            req_out.at[ridx].add(-fin.astype(i32), mode="drop"))


@pytest.mark.parametrize("C,I,R", [
    (256, 8, 32),
    (1000, 33, 2000),          # R > C: in-place request updates
    (512, 16, 64),
])
def test_finish_kernel_matches_refs(C, I, R):
    r = np.random.default_rng(C + I)
    args = _mk_finish_pool(r, C, I, R)
    time, dt = 12.5, 0.25
    got = cloudlet_finish(*args[:8], time, dt, *args[8:], n_inst=I)
    want = _per_field_finish_reference(args, I)
    for name, g, w in zip(got._fields, got, want):
        # single-pass reduction vs per-field seed reference: bit-exact
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"per-field: {name}")


# ---------------------------------------------------------------------------
# prefix-sum segment rank vs the retired sort-based ranking
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,n_seg,block,seed", [
    (1, 1, 128, 0), (48, 8, 16, 1), (300, 5, 128, 2),
    (1024, 64, 128, 3), (777, 3, 256, 4),
])
def test_segment_rank_matches_sorted(n, n_seg, block, seed):
    r = np.random.default_rng(seed)
    keys = jnp.asarray(r.integers(0, n_seg, n), i32)
    mask = jnp.asarray(r.random(n) < 0.6)
    got = segment_rank(keys, mask, n_seg, block=block)
    want = segment_rank_sorted(keys, mask, n_seg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# run_batch ≡ N × run
# ---------------------------------------------------------------------------

def test_run_batch_matches_solo_runs():
    g = diamond(mi=400.0)
    caps = SimCaps(n_clients=16, max_requests=512, max_cloudlets=512,
                   max_instances=8, n_vms=2, d_max=2, max_replicas=2)
    base = SimParams(dt=0.05, n_ticks=200, n_clients=10, spawn_rate=5.0,
                     wait_lo=0.5, wait_hi=1.5, seed=123)
    sim = Simulation(g, caps=caps, params=base)
    sweeps = [dataclasses.replace(base, n_clients=nc, hs_util_hi=th)
              for nc, th in [(4, 0.8), (8, 0.5), (10, 0.8), (16, 0.3)]]
    res_b = sim.run_batch(sweeps)
    for b, p in enumerate(sweeps):
        solo = Simulation(g, caps=caps, params=p).run()
        item = batch_item(res_b, b)
        np.testing.assert_array_equal(
            np.asarray(item.state.requests.response),
            np.asarray(solo.state.requests.response))
        assert int(item.state.counters.spawned) == \
            int(solo.state.counters.spawned)
        assert int(item.state.counters.completed) == \
            int(solo.state.counters.completed)
        np.testing.assert_array_equal(np.asarray(item.trace.completed),
                                      np.asarray(solo.trace.completed))


def test_run_batch_hoisted_scaling_matches_solo_runs():
    """Scaling-enabled sweep: exercises the hoisted-cond batch program
    (scan outside, vmap inside, real lax.cond on the shared cadence)."""
    g = diamond(mi=300.0)
    caps = SimCaps(n_clients=32, max_requests=1024, max_cloudlets=512,
                   max_instances=16, n_vms=4, d_max=2, max_replicas=4)
    base = SimParams(dt=0.05, n_ticks=250, n_clients=20, spawn_rate=10.0,
                     wait_lo=0.5, wait_hi=1.5, scaling_policy=1,
                     scale_interval=40, seed=7)
    tmpl = InstanceTemplate(mips=1000.0, limit_mips=4000.0)
    sim = Simulation(g, caps=caps, params=base, default_template=tmpl)
    sweeps = [dataclasses.replace(base, n_clients=nc, hs_util_hi=th)
              for nc, th in [(8, 0.6), (20, 0.4), (32, 0.2)]]
    res_b = sim.run_batch(sweeps)
    any_scaled = False
    for b, p in enumerate(sweeps):
        solo = Simulation(g, caps=caps, params=p,
                          default_template=tmpl).run()
        item = batch_item(res_b, b)
        np.testing.assert_array_equal(
            np.asarray(item.state.requests.response),
            np.asarray(solo.state.requests.response))
        assert int(item.state.counters.scale_out) == \
            int(solo.state.counters.scale_out)
        any_scaled |= int(solo.state.counters.scale_out) > 0
    assert any_scaled  # the sweep genuinely triggered HS events


def test_run_batch_rejects_structural_sweeps():
    g = diamond(mi=300.0)
    caps = SimCaps(n_clients=8, max_requests=128, max_cloudlets=128,
                   max_instances=4, n_vms=2, d_max=2, max_replicas=2)
    base = SimParams(dt=0.05, n_ticks=50, n_clients=8, spawn_rate=5.0)
    sim = Simulation(g, caps=caps, params=base)
    with pytest.raises(ValueError, match="structural"):
        sim.run_batch([base, dataclasses.replace(base, scaling_policy=1)])
    with pytest.raises(ValueError, match="structural"):
        sim.run_batch([dataclasses.replace(base, max_concurrent=2)])


def test_run_batch_capped_dispatch_path():
    """Sweep max_concurrent (the prefix-sum ranking path) under vmap."""
    g = linear_chain(1, mi=2000.0)
    caps = SimCaps(n_clients=16, max_requests=256, max_cloudlets=128,
                   max_instances=4, n_vms=2, d_max=1, max_replicas=1)
    base = SimParams(dt=0.05, n_ticks=150, n_clients=16, spawn_rate=100.0,
                     wait_lo=0.1, wait_hi=0.2, max_concurrent=2)
    sim = Simulation(g, caps=caps,
                     default_template=InstanceTemplate(mips=1000.0,
                                                       limit_mips=1000.0),
                     params=base)
    sweeps = [dataclasses.replace(base, max_concurrent=m) for m in (1, 2, 3)]
    res_b = sim.run_batch(sweeps)
    for b, p in enumerate(sweeps):
        solo = Simulation(g, caps=caps, params=p,
                          default_template=InstanceTemplate(
                              mips=1000.0, limit_mips=1000.0)).run()
        item = batch_item(res_b, b)
        assert int(np.asarray(item.state.instances.n_exec).max()) <= \
            p.max_concurrent
        np.testing.assert_array_equal(
            np.asarray(item.state.requests.response),
            np.asarray(solo.state.requests.response))

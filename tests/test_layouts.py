"""Mode-keyed pool column registry (DESIGN.md §2.2): PoolLayout resolution,
named-accessor ↔ raw-column round trips, and the four-combo golden matrix
pinning bit-identity through the layout refactor.

Pinned contracts:

 * `resolve_layout` gives each mode combination exactly the columns its
   enabled tick phases declared — the default run carries no fabric or
   resilience columns;
 * every named accessor reads the same storage its layout index points at,
   in every mode; absent columns raise KeyError on read and are skipped on
   write (mode-agnostic spawn sites);
 * all four `network` × `faults` combos reproduce the golden digests
   captured at the commit BEFORE the registry refactor (PR 3 program) —
   shrinking the pool must not move a single bit (the pins are keyed by
   JAX's random stream, see MATRIX_GOLDEN);
 * `run_batch` sweeps bit-match solo runs in the fullest mode
   (fabric + chaos);
 * the pool-level finish wrapper slices the same columns as a direct
   call of the finish reduction, under both the minimal and the full
   layout.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (InstanceTemplate, SimCaps, SimParams, Simulation,
                        batch_item, diamond, resolve_layout)
from repro.core.types import (CL_F_FIELDS, CL_I_FIELDS, Cloudlets,
                              PoolLayout, zeros_state)
from repro.core.scheduler import cloudlet_finish, cloudlet_finish_pool

i32, f32 = jnp.int32, jnp.float32

MODES = [("uniform", "none"), ("uniform", "chaos"),
         ("fabric", "none"), ("fabric", "chaos")]


def _params_for(network: str, faults: str, **over) -> SimParams:
    kw = dict(network=network, faults=faults)
    kw.update(over)
    return SimParams(**kw)


# ---------------------------------------------------------------------------
# layout resolution: phases declare columns, modes enable phases
# ---------------------------------------------------------------------------

def test_default_layout_is_minimal():
    L = resolve_layout(SimParams())
    assert L.i_fields == ("status", "req", "service", "inst",
                          "wait_ticks", "depth")
    assert L.f_fields == ("length", "rem", "arrival", "start")


def test_mode_columns_appear_only_with_their_phase():
    for network, faults in MODES:
        L = resolve_layout(_params_for(network, faults))
        assert ("src_host" in L) == (network == "fabric")
        assert ("rem_bytes" in L) == (network == "fabric")
        for col in ("attempt", "edge", "src_inst"):
            assert (col in L) == (faults == "chaos"), (network, faults, col)
    # egress shaping is a Transit sub-feature: src_inst joins the layout
    # in fabric mode even without chaos
    L = resolve_layout(_params_for("fabric", "none", egress_shaping=True))
    assert "src_inst" in L and "attempt" not in L
    # ... but shaping outside fabric mode changes nothing (the clamp only
    # exists inside the Transit phase)
    assert resolve_layout(_params_for("uniform", "none",
                                      egress_shaping=True)) == \
        resolve_layout(_params_for("uniform", "none"))


def test_layout_storage_order_follows_registry():
    """Column storage order is the registry order restricted to the active
    set, so the full layout is exactly the pre-refactor fixed layout."""
    full = resolve_layout(_params_for("fabric", "chaos",
                                      egress_shaping=True))
    assert full.i_fields == CL_I_FIELDS
    assert full.f_fields == CL_F_FIELDS
    for network, faults in MODES:
        L = resolve_layout(_params_for(network, faults))
        assert L.i_fields == tuple(n for n in CL_I_FIELDS if n in L)
        assert L.f_fields == tuple(n for n in CL_F_FIELDS if n in L)


@pytest.mark.parametrize("network,faults", MODES)
def test_accessor_roundtrip_every_column_every_mode(network, faults):
    """Named accessor ↔ raw column round trip: every registered column of
    every mode's layout reads exactly its storage slice; absent columns
    raise on read and are skipped on write."""
    params = _params_for(network, faults)
    caps = SimCaps(n_clients=4, max_requests=16, max_cloudlets=32,
                   max_instances=4, n_vms=2, d_max=2)
    state = zeros_state(caps, params, jax.random.PRNGKey(0), n_services=3)
    cl = state.cloudlets
    L = cl.layout
    r = np.random.default_rng(7)
    cl = cl.replace(
        ints=jnp.asarray(r.integers(-2, 9, cl.ints.shape), i32),
        flts=jnp.asarray(r.normal(size=cl.flts.shape), f32))
    for name in L.i_fields:
        np.testing.assert_array_equal(np.asarray(getattr(cl, name)),
                                      np.asarray(cl.ints[:, L.i(name)]))
    for name in L.f_fields:
        np.testing.assert_array_equal(np.asarray(getattr(cl, name)),
                                      np.asarray(cl.flts[:, L.f(name)]))
    for name in CL_I_FIELDS + CL_F_FIELDS:
        if name in L:
            continue
        with pytest.raises(KeyError, match=name):
            getattr(cl, name)
        # writes of absent-but-registered columns are skipped in place
        same = cl.with_cols(**{name: 0})
        np.testing.assert_array_equal(np.asarray(same.ints),
                                      np.asarray(cl.ints))
        np.testing.assert_array_equal(np.asarray(same.flts),
                                      np.asarray(cl.flts))
    with pytest.raises(TypeError, match="unknown"):
        cl.with_cols(not_a_column=1)


def test_layout_is_static_aux_data():
    """The layout rides pytrees as aux data: tree_map preserves it and two
    states of the same mode share one (hashable) layout object."""
    params = _params_for("fabric", "chaos")
    caps = SimCaps(n_clients=4, max_requests=16, max_cloudlets=32,
                   max_instances=4, n_vms=2, d_max=2)
    state = zeros_state(caps, params, jax.random.PRNGKey(0))
    mapped = jax.tree_util.tree_map(lambda x: x, state)
    assert mapped.cloudlets.layout is state.cloudlets.layout
    assert isinstance(state.cloudlets.layout, PoolLayout)
    assert hash(resolve_layout(params)) == hash(state.cloudlets.layout)


# ---------------------------------------------------------------------------
# golden matrix: all four mode combos bit-identical through the refactor
# ---------------------------------------------------------------------------

from test_network import _digest_f32  # one digest scheme for all goldens


def matrix_sim(network: str, faults: str, **overrides):
    """The golden-matrix scenario; ``overrides`` lets observation-only
    knobs (telemetry, tests/test_obs.py) run the same scenario."""
    caps = SimCaps(n_clients=16, max_requests=512, max_cloudlets=512,
                   max_instances=8, n_vms=4, d_max=2, max_replicas=2)
    kw = dict(dt=0.05, n_ticks=300, n_clients=12, spawn_rate=5.0,
              wait_lo=0.5, wait_hi=1.5, seed=3,
              network=network, faults=faults)
    if network == "fabric":
        kw.update(nic_egress_mbps=50.0, nic_ingress_mbps=50.0)
    else:
        kw.update(net_latency_s=0.05)
    if faults == "chaos":
        kw.update(host_mtbf_s=20.0, host_mttr_s=5.0, retry_timeout_s=3.0,
                  retry_budget=2, inst_kill_rate=0.01)
    kw.update(overrides)
    params = SimParams(**kw)
    tmpl = InstanceTemplate(mips=8000.0, limit_mips=16000.0, replicas=2)
    return Simulation(diamond(mi=400.0), caps=caps, params=params,
                      default_template=tmpl,
                      vm_mips=np.full(4, 64000.0, np.float32))


def simulated_leaves(res) -> dict:
    """Every leaf of a run's state and trace that the simulation computes,
    keyed by its path: the observers' own carries (``telemetry``,
    ``alerts``) are left out."""
    st = res.state._replace(telemetry=None, alerts=None)
    return {jax.tree_util.keystr(path): np.asarray(x)
            for path, x in jax.tree_util.tree_leaves_with_path(
                (st, res.trace))}


def assert_same_simulation(got, want) -> None:
    """Bit-for-bit equality of every simulated leaf of two runs."""
    g, w = simulated_leaves(got), simulated_leaves(want)
    assert g.keys() == w.keys()
    for k in w:
        assert g[k].dtype == w[k].dtype and g[k].shape == w[k].shape, k
        assert g[k].tobytes() == w[k].tobytes(), k


# Pinned digests of matrix_sim, keyed by JAX's random stream
# (``jax_threefry_partitionable``), which draws different bits for the
# same key: JAX made the partitionable stream the default in 0.5.
#
# False: captured at commit 50ee839 (PR 3, fixed 10-int/5-float layout).
#   A CPU run of every commit from 50ee839 to 797987c with
#   JAX_THREEFRY_PARTITIONABLE=0 reproduces resp and all six counters
#   exactly; its used_mips digests sit 1 to 8 units (float rounding of the
#   host) off the pins, at every commit alike.  No commit moved a number.
# True: captured at commit 797987c with JAX 0.9.0 on an x86-64 CPU with
#   AVX-512; the counters are exact on any host, the two float digests
#   (resp, used_mips) may differ on another CPU.
MATRIX_GOLDEN = {
    False: {
        ("uniform", "none"): dict(resp=1306795296637, completed=157,
                                  spawned=794, finished=789,
                                  used_mips=353555764098, transits=0,
                                  failed_attempts=0, retries=0),
        ("uniform", "chaos"): dict(resp=1530248430121, completed=54,
                                   spawned=1002, finished=296,
                                   used_mips=346459279954, transits=0,
                                   failed_attempts=517, retries=388),
        ("fabric", "none"): dict(resp=1292572014442, completed=163,
                                 spawned=830, finished=822,
                                 used_mips=355715694613, transits=606,
                                 failed_attempts=0, retries=0),
        ("fabric", "chaos"): dict(resp=1477918938445, completed=78,
                                  spawned=803, finished=626,
                                  used_mips=348111040792, transits=289,
                                  failed_attempts=80, retries=79),
    },
    True: {
        ("uniform", "none"): dict(resp=1306688150490, completed=157,
                                  spawned=809, finished=798,
                                  used_mips=353596630452, transits=0,
                                  failed_attempts=0, retries=0),
        ("uniform", "chaos"): dict(resp=1428723020766, completed=101,
                                   spawned=796, finished=665,
                                   used_mips=353248719257, transits=0,
                                   failed_attempts=30, retries=30),
        ("fabric", "none"): dict(resp=1296873436538, completed=161,
                                 spawned=813, finished=808,
                                 used_mips=355857880820, transits=599,
                                 failed_attempts=0, retries=0),
        ("fabric", "chaos"): dict(resp=1507999849186, completed=64,
                                  spawned=813, finished=486,
                                  used_mips=347704935484, transits=151,
                                  failed_attempts=155, retries=135),
    },
}


@pytest.mark.parametrize("network,faults", MODES)
def test_mode_matrix_bit_identical_golden(network, faults):
    res = matrix_sim(network, faults).run()
    st = res.state
    golden = MATRIX_GOLDEN[bool(jax.config.jax_threefry_partitionable)]
    want = golden[(network, faults)]
    # the exact counters first, then the float digests
    assert int(st.counters.completed) == want["completed"]
    assert int(st.counters.spawned) == want["spawned"]
    assert int(st.counters.finished) == want["finished"]
    assert int(st.net.transits) == want["transits"]
    assert int(st.fstats.failed_attempts) == want["failed_attempts"]
    assert int(st.fstats.retries) == want["retries"]
    assert _digest_f32(st.requests.response) == want["resp"]
    assert _digest_f32(res.trace.used_mips) == want["used_mips"]


def test_fabric_chaos_sweep_bitmatches_solo():
    """run_batch under the fullest layout (fabric + chaos): every sweep
    point still bit-matches its solo run after the refactor."""
    sim = matrix_sim("fabric", "chaos")
    base = sim.params
    sweeps = [dataclasses.replace(base, host_mtbf_s=m, nic_egress_mbps=b,
                                  nic_ingress_mbps=b)
              for m, b in ((60.0, 50.0), (15.0, 10.0))]
    res_b = sim.run_batch(sweeps)
    for b, p in enumerate(sweeps):
        solo = Simulation(
            sim.graph, caps=sim.caps, params=p,
            default_template=InstanceTemplate(mips=8000.0,
                                              limit_mips=16000.0,
                                              replicas=2),
            vm_mips=np.full(4, 64000.0, np.float32)).run()
        item = batch_item(res_b, b)
        np.testing.assert_array_equal(
            np.asarray(item.state.requests.response),
            np.asarray(solo.state.requests.response))
        assert int(item.state.net.transits) == int(solo.state.net.transits)
        assert int(item.state.fstats.failed_attempts) == \
            int(solo.state.fstats.failed_attempts)


# ---------------------------------------------------------------------------
# fused finish reduction through the pool wrapper: minimal vs full layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lname,network,faults", [
    ("minimal", "uniform", "none"),
    ("full", "fabric", "chaos"),
])
def test_finish_kernel_pool_wrapper_both_layouts(lname, network, faults):
    """cloudlet_finish_pool slices its inputs through the layout: it must
    agree with cloudlet_finish fed the same columns directly, for both
    the minimal and the full layout."""
    layout = resolve_layout(_params_for(network, faults))
    C, I, R = 256, 8, 64
    r = np.random.default_rng(11)
    ints = np.zeros((C, len(layout.i_fields)), np.int32)
    flts = np.zeros((C, len(layout.f_fields)), np.float32)
    cols = dict(
        status=r.choice([0, 1, 2], size=C, p=[0.3, 0.2, 0.5]),
        req=r.integers(-1, R, C), inst=r.integers(-1, I, C),
        depth=r.integers(0, 6, C),
        rem=r.uniform(0.1, 500.0, C), arrival=r.uniform(0.0, 10.0, C),
        start=r.uniform(-1.0, 12.0, C))
    for n, v in cols.items():
        if n in layout.i_fields:
            ints[:, layout.i(n)] = v
        else:
            flts[:, layout.f(n)] = v
    cl = Cloudlets(jnp.asarray(ints), jnp.asarray(flts), layout)
    rate = jnp.asarray(r.uniform(0.0, 300.0, C), f32)
    reqf = jnp.asarray(r.uniform(0.0, 12.0, R), f32)
    reqc = jnp.asarray(r.integers(0, 4, R), i32)
    reqo = jnp.asarray(r.integers(0, 8, R), i32)
    time, dt = 12.5, 0.25
    got = cloudlet_finish_pool(cl, rate, time, dt, reqf, reqc, reqo,
                               n_inst=I)
    want = cloudlet_finish(
        jnp.asarray(cols["status"], i32), jnp.asarray(cols["rem"], f32),
        jnp.asarray(cols["inst"], i32), jnp.asarray(cols["req"], i32),
        jnp.asarray(cols["arrival"], f32), jnp.asarray(cols["start"], f32),
        jnp.asarray(cols["depth"], i32), rate, time, dt,
        reqf, reqc, reqo, n_inst=I)
    for name in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=f"{lname}: {name}")

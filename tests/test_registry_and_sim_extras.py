"""File registry (paper Fig 3), SockShop config, scaling/migration
behaviours, build-time bounds validation, and kernel-path equivalence
of the engine tick."""
import json

import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from repro.configs import sockshop
from repro.core import (InstanceTemplate, SimCaps, SimParams, Simulation,
                        diamond, policies, register, summarize)
from repro.core.app import validate_app
from repro.core.graph import build_graph
from repro.core.types import INST_ON


def test_registry_json_yaml_roundtrip(tmp_path):
    app = tmp_path / "app.json"
    inst = tmp_path / "instances.yaml"
    app.write_text(json.dumps(sockshop.app_spec()))
    inst.write_text(yaml.safe_dump(sockshop.instance_spec(share=800.0)))
    caps = SimCaps(n_clients=16, max_requests=1024, max_cloudlets=2048,
                   max_instances=32, n_vms=4, d_max=5, max_replicas=2)
    params = SimParams(dt=0.1, n_ticks=300, n_clients=10, spawn_rate=2.0,
                       wait_lo=2.0, wait_hi=6.0)
    sim = register(str(app), str(inst), caps=caps, params=params)
    assert sim.graph.n_services == 13
    assert sim.graph.n_apis == 5
    # YAML requests.share becomes the instance MIPS
    assert float(np.asarray(sim.app.tmpl_mips)[0]) == 800.0
    rep = summarize(sim, sim.run())
    assert rep.completed_requests > 0


def test_sockshop_graph_structure():
    sim = sockshop.make_sim(n_clients=10, duration_s=30.0)
    g = sim.graph
    # paper Fig 8: POST /orders triggers the deep shipping chain
    orders = g.service_id("orders")
    chains = g.chains_from(orders)
    leaves = {c[-1] for c in chains}
    assert g.service_id("queue-master") in leaves
    assert g.depth >= 3


def test_hs_scales_out_under_load():
    sim = sockshop.make_sim(n_clients=300, duration_s=120.0,
                            scaling_policy=policies.SCALE_HORIZONTAL,
                            share=400.0, hs_util_hi=0.5, hs_util_lo=0.05,
                            util_ema=0.2)
    res = sim.run()
    assert int(res.state.counters.scale_out) > 0
    on = np.asarray(res.state.instances.status) == INST_ON
    assert on.sum() > 13          # replicas were added


def test_vs_raises_mips_under_load():
    sim = sockshop.make_sim(n_clients=300, duration_s=120.0,
                            scaling_policy=policies.SCALE_VERTICAL,
                            share=400.0, vs_util_hi=0.5, vs_util_lo=0.05,
                            util_ema=0.2)
    res = sim.run()
    assert int(res.state.counters.scale_up) > 0
    inst = res.state.instances
    on = np.asarray(inst.status) == INST_ON
    assert (np.asarray(inst.mips)[on] > np.asarray(
        inst.request_mips)[on] + 1).any()


def test_migration_moves_instance():
    g = diamond(mi=500.0)
    caps = SimCaps(n_clients=8, max_requests=512, max_cloudlets=512,
                   max_instances=8, n_vms=3, d_max=2, max_replicas=2)
    params = SimParams(dt=0.05, n_ticks=400, n_clients=8, spawn_rate=8.0,
                       wait_lo=0.5, wait_hi=1.0, migration_enabled=True,
                       mig_vm_util_hi=0.5, scale_interval=20)
    # most-available placement stacks all four instances on VM0 (its free
    # capacity stays the largest throughout) → 90 % allocation pressure;
    # VM1 is the only target that ends up cooler than the source
    # (anti-ping-pong hysteresis in placement.migrate)
    sim = Simulation(g, caps=caps, params=params,
                     default_template=InstanceTemplate(mips=900.0,
                                                       limit_mips=900.0),
                     vm_mips=np.array([4000.0, 1250.0, 1200.0], np.float32),
                     vm_ram=np.array([4096.0, 4096.0, 4096.0], np.float32))
    res = sim.run()
    assert int(res.state.counters.migrations) > 0
    vms = np.asarray(res.state.instances.vm)
    on = np.asarray(res.state.instances.status) == INST_ON
    assert len(set(vms[on].tolist())) > 1


# ---------------------------------------------------------------------------
# Build-time bounds validation (DESIGN.md §8): every id table the jitted
# tick indexes with is range-checked BEFORE tracing, with errors naming
# the offending entry.
# ---------------------------------------------------------------------------

_TINY_CAPS = SimCaps(n_clients=8, max_requests=128, max_cloudlets=128,
                     max_instances=8, n_vms=2, d_max=2, max_replicas=2)
_TINY_PARAMS = SimParams(dt=0.05, n_ticks=4, n_clients=4, spawn_rate=4.0,
                         wait_lo=0.1, wait_hi=0.3)


def _tiny_app():
    sim = Simulation(diamond(mi=200.0), caps=_TINY_CAPS,
                     params=_TINY_PARAMS)
    return sim.app


def test_register_rejects_replica_overflow():
    inst = sockshop.instance_spec(share=800.0)
    inst["instances"][0]["replicas"] = 99
    caps = SimCaps(n_clients=16, max_requests=1024, max_cloudlets=2048,
                   max_instances=32, n_vms=4, d_max=5, max_replicas=2)
    with pytest.raises(ValueError, match="declares replicas=99"):
        register(sockshop.app_spec(), inst, caps=caps)


def test_register_rejects_zone_count_mismatch():
    spec = sockshop.app_spec()
    spec["zones"] = [0, 0, 1]          # 3 entries for a 4-host cluster
    caps = SimCaps(n_clients=16, max_requests=1024, max_cloudlets=2048,
                   max_instances=32, n_vms=4, d_max=5, max_replicas=2)
    with pytest.raises(ValueError, match='"zones" lists 3 entries'):
        register(spec, sockshop.instance_spec(share=800.0), caps=caps)


def test_build_rejects_out_of_range_host_zone():
    with pytest.raises(ValueError, match="host_zone"):
        Simulation(diamond(mi=200.0), caps=_TINY_CAPS,
                   params=_TINY_PARAMS,
                   host_zone=np.asarray([0, 7], np.int32))


def test_build_rejects_out_degree_beyond_d_max():
    caps = SimCaps(n_clients=8, max_requests=128, max_cloudlets=128,
                   max_instances=8, n_vms=2, d_max=1, max_replicas=2)
    # diamond's entry fans out to two callees: out-degree 2 > d_max=1
    with pytest.raises(ValueError, match="out-degree"):
        Simulation(diamond(mi=200.0), caps=caps, params=_TINY_PARAMS)


def test_build_accepts_chain_deeper_than_d_max():
    # d_max caps succ-table WIDTH (out-degree), not chain depth: a
    # linear depth-3 chain with d_max=1 is legal (cf. test_critical_path
    # which runs one through the engine) and must pass validation.
    names = ["a", "b", "c", "d"]
    chain = build_graph(names,
                        {"a": ["b"], "b": ["c"], "c": ["d"], "d": []},
                        [("api", "a", 1.0)],
                        {n: 200.0 for n in names},
                        {n: 20.0 for n in names})
    caps = SimCaps(n_clients=8, max_requests=128, max_cloudlets=128,
                   max_instances=8, n_vms=2, d_max=1, max_replicas=2)
    sim = Simulation(chain, caps=caps, params=_TINY_PARAMS)
    validate_app(sim.app, caps)         # no exception


def test_validate_app_rejects_out_of_range_succ_id():
    app = _tiny_app()
    succ = np.asarray(app.succ).copy()
    succ[0, 0] = 99
    with pytest.raises(ValueError, match="succ table ids"):
        validate_app(app._replace(succ=jnp.asarray(succ)), _TINY_CAPS)


def test_validate_app_rejects_call_graph_cycle():
    app = _tiny_app()
    succ = np.asarray(app.succ).copy()
    entry = int(np.asarray(app.api_entry).max())
    succ[entry, 0] = entry              # entry service calls itself
    with pytest.raises(ValueError, match="cycle"):
        validate_app(app._replace(succ=jnp.asarray(succ)), _TINY_CAPS)


def test_validate_app_rejects_undersized_edge_table():
    app = _tiny_app()
    with pytest.raises(ValueError, match="edge tables"):
        validate_app(app._replace(edge_retry=app.edge_retry[:-1]),
                     _TINY_CAPS)


def test_validate_app_rejects_api_without_entry():
    app = _tiny_app()
    with pytest.raises(ValueError, match="no entry service"):
        validate_app(
            app._replace(api_entry=jnp.full_like(app.api_entry, -1)),
            _TINY_CAPS)


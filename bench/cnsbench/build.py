"""Build a configuration's ``Simulation`` through the program's public
entries (``Simulation``, ``register``, ``SimCaps``, ``SimParams``,
``InstanceTemplate``, ``build_graph``).

Two kinds of configuration file:

* ``capacity``: a paper Table 2 case, given by its object counts and
  sized by the arithmetic below (a copy of the sizing that
  ``benchmarks/bench_capacity.build_case`` applies, kept here so the
  yardstick does not move when that file does);
* ``registry``: the paper's file registry (Fig 3): an application
  document, an instance document, capacities, parameters and the node
  list, handed to ``register`` as they stand.

A traffic file's ``params`` replace ``SimParams`` fields of the built
deployment (load, spawn rate); it never changes capacities.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


def capacity_sizes(t2: dict) -> dict:
    """Every size of a Table 2 case, from its object counts."""
    n_req, n_svc, reps = t2["n_requests"], t2["n_services"], t2["replicas"]
    fanout = max(int(t2.get("fanout", 1)), 1)
    dt, mi = float(t2["dt"]), float(t2["mi"])
    n_inst = n_svc * reps
    avg_wait_ticks = (t2["wait_lo"] + t2["wait_hi"]) / 2.0 / dt
    # requests admitted per tick: the pool holds ~2 ticks of arrivals with
    # 2x head-room; then enough ticks to admit everything and drain
    k_fire = max(int(math.ceil(n_req / t2["target_ticks"])), 1)
    if 5 * k_fire * fanout > 2 * (1 << 18):
        k_fire = max(2 * (1 << 18) // (5 * fanout), 1)
    pool = int(min(max(4 * k_fire * fanout, 1 << 12), 1 << 18))
    n_clients = int(min(max(k_fire * avg_wait_ticks, 64), 1 << 16))
    fire_rate = min(k_fire, n_clients / avg_wait_ticks)
    n_ticks = int(n_req / fire_rate * 1.25) + 60
    # instance speed: each tick's per-instance batch drains in ~0.4 ticks
    a_i = fire_rate * fanout / n_inst
    mips = max(a_i, 0.4) * mi / (0.4 * dt)
    n_vms = max(n_inst // 64, 4)
    return dict(n_instances=n_inst, n_vms=n_vms, k_fire=k_fire,
                max_cloudlets=pool, n_clients=n_clients,
                max_requests=n_req + n_clients + 8, n_ticks=n_ticks,
                mips=mips, vm_mips=2.0 * mips * n_inst / n_vms + 1e4)


def _capacity(cfg: dict, overrides: dict):
    from repro.core import (InstanceTemplate, SimCaps, SimParams,
                            Simulation, build_graph)

    t2 = cfg["table2"]
    z = capacity_sizes(t2)
    names = [f"s{i}" for i in range(t2["n_services"])]
    mi = float(t2["mi"])
    graph = build_graph(names, {}, [("api", names[0], 1.0)],
                        {n: mi for n in names}, d_max=1)
    caps = SimCaps(n_clients=z["n_clients"], max_requests=z["max_requests"],
                   max_cloudlets=z["max_cloudlets"],
                   max_instances=z["n_instances"], n_vms=z["n_vms"],
                   d_max=1, max_replicas=t2["replicas"], k_fire=z["k_fire"])
    params = SimParams(dt=t2["dt"], n_ticks=z["n_ticks"],
                       n_clients=z["n_clients"],
                       spawn_rate=z["n_clients"] / 5.0,
                       wait_lo=t2["wait_lo"], wait_hi=t2["wait_hi"],
                       num_limit=t2["n_requests"], seed=0)
    params = dataclasses.replace(params, **overrides)
    tmpl = InstanceTemplate(mips=z["mips"], limit_mips=2 * z["mips"],
                            ram=1.0, limit_ram=2.0, bw=100.0,
                            replicas=t2["replicas"])
    vm_mips = np.full(z["n_vms"], z["vm_mips"], np.float32)
    vm_ram = np.full(z["n_vms"], 1e9, np.float32)
    return Simulation(graph, caps=caps, params=params,
                      default_template=tmpl, vm_mips=vm_mips, vm_ram=vm_ram)


# placement policy names of the registry documents -> core.policies ids
_PLACEMENT = {"most_available": 0, "first_fit": 1, "best_fit": 2,
              "spread": 3}


def _registry(cfg: dict, overrides: dict):
    from repro.core import SimCaps, SimParams, register

    params = dict(cfg["params"], **overrides)
    return register(cfg["app"], cfg["instances"],
                    caps=SimCaps(**cfg["caps"]),
                    params=SimParams(**params),
                    vm_mips=np.asarray(cfg["vm_mips"], np.float32),
                    vm_ram=np.asarray(cfg["vm_ram"], np.float32),
                    placement_policy=_PLACEMENT[cfg["placement"]])


KINDS = {"capacity": _capacity, "registry": _registry}


def build(cfg: dict, traffic: dict):
    """The ``Simulation`` a cell's jobs run."""
    return KINDS[cfg["kind"]](cfg, dict(traffic.get("params", {})))

"""Per-API call trees and conditional calls (DESIGN.md §2.5), on the
DeathStarBench social-network deployment of the benchmark
(``bench/configs/dsb-social.json``) and on small registry apps.

* the deployment, cut to a few clients, agrees with its plain reference
  (``bench/configs/dsb-social.reference.py``): exact counts, responses to
  float32 rounding;
* a request of each API spawns only its API's calls;
* p = 0 and p = 1 give what the tree without and with the edge gives;
* graphs that route nothing run Derive as before, bit for bit;
* malformed trees and probabilities are refused at build.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import sys

import numpy as np
import pytest

from repro.core import SimCaps, SimParams, register
from repro.core.critical_path import critical_path
from repro.core.registry import graph_from_spec

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(BENCH / "tests") not in sys.path:
    sys.path.insert(0, str(BENCH / "tests"))

import bench_testkit as tk  # noqa: E402
from cnsbench import build, jobs as jobsmod, spec  # noqa: E402

# the deployment cut to a CPU test: ~400 clients, 200 ticks (2 s)
TINY_CAPS = dict(n_clients=512, max_requests=8000, max_cloudlets=1024)
TINY_TRAFFIC = dict(name="tiny", kind="solo",
                    params=dict(n_clients=400, spawn_rate=1000.0,
                                wait_lo=0.5, wait_hi=1.5))


def tiny_dsb(n_ticks: int = 200) -> dict:
    cfg = json.loads((BENCH / "configs" / "dsb-social.json").read_text())
    cfg["caps"].update(TINY_CAPS)
    cfg["params"]["n_ticks"] = n_ticks
    return cfg


def _reference():
    return spec.load_module(BENCH / "configs" / "dsb-social.reference.py")


def _api_calls(api: dict) -> tuple:
    """(calls always made, conditional calls) of an API's tree, the root
    call included."""
    edges = [c for cs in api["tree"]["calls"].values() for c in cs]
    cond = sum(1 for c in edges if isinstance(c, dict) and c["p"] < 1)
    return 1 + len(edges) - cond, cond


@pytest.fixture(scope="module")
def dsb_jobs():
    cfg = tiny_dsb()
    ref = _reference()
    sim = build.build(cfg, TINY_TRAFFIC)
    jobs = jobsmod.Jobs(sim, TINY_TRAFFIC, 2 ** 31 + 77, extra=ref.READBACK)
    return cfg, sim, [jobs.run(k) for k in range(2)]


def test_dsb_social_agrees_with_its_reference(dsb_jobs):
    cfg, _, jobs = dsb_jobs
    ref = _reference()
    assert all(not j.failures for j in jobs)
    got = ref.compare(cfg, TINY_TRAFFIC, jobs)
    assert got.pop("response_gap_ms") <= 0.01
    assert got == dict(admitted_ticks_differing=0,
                       finished_requests_differing=0,
                       calls_per_request_differing=0)


def test_each_api_spawns_only_its_tree(dsb_jobs):
    cfg, sim, jobs = dsb_jobs
    res = sim.run(seed=jobs[0].seed)
    api = np.asarray(res.state.requests.api)
    spawned = np.asarray(res.state.requests.spawned)
    done = np.asarray(res.state.requests.response) >= 0
    for a, doc in enumerate(cfg["app"]["apis"]):
        fixed, cond = _api_calls(doc)
        n = spawned[(api == a) & done]
        assert len(n) > 10
        assert n.min() >= fixed and n.max() <= fixed + cond, doc["name"]
    ctr = res.state.counters
    assert int(ctr.branch_taken) > 0 and int(ctr.branch_skipped) > 0
    # the record of the run carries the counters, as the state holds them
    from repro.obs import hostspans
    assert hostspans.last().counts == dict(
        branch_taken=int(ctr.branch_taken),
        branch_skipped=int(ctr.branch_skipped))


# --- a small gateway app ---------------------------------------------------

def _app(a_calls, b_calls=("b",)) -> dict:
    """gateway -> {a, b}; a -> {cache, db}; API "A" takes a's branch, API
    "B" b's.  ``a_calls`` are a's callees in A's tree."""
    svcs = [dict(name=n, mi=mi) for n, mi in
            (("gateway", 4.0), ("a", 6.0), ("b", 8.0),
             ("cache", 2.0), ("db", 30.0), ("wide", 1.0))]
    # one service of out-degree 3, so cutting a's db edge keeps d_max
    svcs[5]["calls"] = ["cache", "db", "b"]
    apis = [dict(name="A", weight=1.0, tree=dict(root="gateway", calls={
                "gateway": ["a"], "a": list(a_calls)})),
            dict(name="B", weight=1.0, tree=dict(root="gateway", calls={
                "gateway": list(b_calls)}))]
    return dict(services=svcs, apis=apis)


def _run(app: dict, n_ticks: int = 120, limit: int = 150):
    caps = SimCaps(n_clients=64, max_requests=256, max_cloudlets=512,
                   max_instances=12, n_vms=2, d_max=3, max_replicas=2)
    params = SimParams(dt=0.05, n_ticks=n_ticks, n_clients=48,
                       spawn_rate=20.0, wait_lo=0.2, wait_hi=0.6,
                       num_limit=limit, seed=5)
    return register(app, caps=caps, params=params,
                    vm_mips=np.full(2, 8000.0, np.float32),
                    vm_ram=np.full(2, 65536.0, np.float32)).run(seed=11)


def _outputs(res) -> dict:
    st = res.state
    return dict(response=np.asarray(st.requests.response),
                spawned=np.asarray(st.requests.spawned),
                finished=np.asarray(st.svc_stats.finished),
                generated=np.asarray(res.trace.generated))


def test_a_request_calls_only_its_apis_services():
    res = _run(_app(["cache", "db"]))
    st = res.state
    api = np.asarray(st.requests.api)[: int(st.requests.count)]
    assert (np.asarray(st.requests.response)[: len(api)] >= 0).all()
    n_a, n_b = int((api == 0).sum()), int((api == 1).sum())
    assert n_a > 20 and n_b > 20
    fin = dict(zip(["gateway", "a", "b", "cache", "db", "wide"],
                   np.asarray(st.svc_stats.finished).tolist()))
    assert fin == dict(gateway=n_a + n_b, a=n_a, b=n_b, cache=n_a, db=n_a,
                       wide=0)
    spawned = np.asarray(st.requests.spawned)[: len(api)]
    assert (spawned[api == 0] == 4).all() and (spawned[api == 1] == 2).all()
    # every call here is made with p = 1: nothing is drawn
    assert int(st.counters.branch_taken) == 0
    assert int(st.counters.branch_skipped) == 0


@pytest.mark.parametrize("p,same_as", [(1.0, ["cache", "db"]),
                                       (0.0, ["cache"])],
                         ids=["p1_edge_kept", "p0_edge_removed"])
def test_p0_and_p1_are_the_edge_removed_and_kept(p, same_as):
    got = _outputs(_run(_app(["cache", dict(to="db", p=p)])))
    want = _outputs(_run(_app(same_as)))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_conditional_call_is_drawn_per_call():
    res = _run(_app(["cache", dict(to="db", p=0.3)]))
    st = res.state
    taken = int(st.counters.branch_taken)
    skipped = int(st.counters.branch_skipped)
    fin = np.asarray(st.svc_stats.finished)
    assert taken == fin[4] and taken + skipped == fin[1]
    assert 0.15 < taken / (taken + skipped) < 0.45


def test_routed_path_taking_every_call_matches_plain_derive():
    """One API, whose tree is its services' calls: the routed Derive
    (every p = 1) gives the plain Derive's outputs, bit for bit."""
    plain = dict(services=[dict(name="gateway", mi=4.0, calls=["a"]),
                           dict(name="a", mi=6.0, calls=["cache", "db"]),
                           dict(name="cache", mi=2.0),
                           dict(name="db", mi=30.0)],
                 apis=[dict(name="A", entry="gateway")])
    routed = json.loads(json.dumps(plain))
    routed["apis"] = [dict(name="A", tree=dict(root="gateway", calls={
        "gateway": ["a"], "a": ["cache", "db"]}))]
    assert graph_from_spec(plain).route_p is None
    assert graph_from_spec(routed).route_p is not None
    got, want = _outputs(_run(routed)), _outputs(_run(plain))
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.mark.parametrize("spec_edit,error", [
    (lambda a: a["apis"][0]["tree"]["calls"].update(b=["wide"]),
     ValueError),
    (lambda a: a["apis"][0]["tree"]["calls"].update(cache=["gateway"]),
     ValueError),
    (lambda a: a["apis"][0]["tree"]["calls"]["a"].append(
        dict(to="b", p=1.5)), ValueError),
    (lambda a: a["apis"][0].update(entry="a"), ValueError),
    (lambda a: a["apis"][0]["tree"]["calls"].update(a=["nowhere"]),
     KeyError),
], ids=["unreached_caller", "calls_its_root", "p_above_one",
        "entry_is_not_root", "unknown_callee"])
def test_malformed_trees_are_refused_at_build(spec_edit, error):
    app = _app(["cache", "db"])
    spec_edit(app)
    with pytest.raises(error):
        graph_from_spec(app)


def test_critical_path_refuses_routed_graphs():
    with pytest.raises(ValueError, match="routes calls"):
        critical_path(graph_from_spec(_app(["cache"])),
                      np.ones(6, np.float32), 0)


# --- the existing cells take today's path ----------------------------------

def _digest(x) -> str:
    a = np.ascontiguousarray(np.asarray(x))
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


# Outputs of the benchmark's two earlier configurations, cut by
# bench_testkit, at seed 2718281, recorded on the CPU before per-API
# routing existed.
BEFORE_ROUTING = {
    ("case1b", "seeds"): dict(
        response="6ce350c718491526", spawned="d542127eb7127402",
        generated="d81ac1a341165e7d", used_mips="2cc21cca5700cb9d",
        completed=2000, spawned_total=2000, finished=2000),
    ("sockshop-hs", "u300"): dict(
        response="1b828cfc93a07bdc", spawned="4bb98b91c47393c0",
        generated="87704a65d8e9ca8a", used_mips="70e9155d187657d0",
        completed=199, spawned_total=825, finished=818),
}


@pytest.mark.parametrize("config,traffic", sorted(BEFORE_ROUTING))
def test_unrouted_cells_are_bit_identical(config, traffic):
    cfg = tk.tiny_config(config)
    t = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    sim = build.build(cfg, t)
    assert sim.app.route_p is None
    res = sim.run(seed=2718281)
    st = res.state
    got = dict(response=_digest(st.requests.response),
               spawned=_digest(st.requests.spawned),
               generated=_digest(res.trace.generated),
               used_mips=_digest(res.trace.used_mips),
               completed=int(st.counters.completed),
               spawned_total=int(st.counters.spawned),
               finished=int(st.counters.finished))
    assert got == BEFORE_ROUTING[(config, traffic)]
    assert int(st.counters.branch_taken) == 0
    from repro.obs import hostspans
    assert hostspans.last().counts == {}


def test_instance_groups_match_their_own_services():
    cfg = tiny_dsb()
    sim = build.build(cfg, TINY_TRAFFIC)
    reps = {g["labels"][0]: g["replicas"]
            for g in cfg["instances"]["instances"]}
    assert np.asarray(sim.app.tmpl_replicas).tolist() == [
        reps[n] for n in sim.graph.names]

"""Host spans and compile counters of ``Simulation.run`` / ``run_batch``
(``obs/hostspans.py``, ``Simulation.stats()``)."""
import dataclasses
import glob
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro.analysis.layout_check import _tiny_sim
from repro.core import Simulation
from repro.obs import hostspans

STAGES = {
    "run": ["sim/init_state", "sim/unalias", "sim/dyn_params", "sim/lookup",
            "sim/dispatch", "sim/wait"],
    "run_batch": ["sim/dyn_params", "sim/init_state", "sim/lookup",
                  "sim/dispatch", "sim/wait"],
}


def _call(sim, kind, seed):
    if kind == "run":
        return sim.run(seed=seed)
    return sim.run_batch([sim.params] * 2, seed=seed)


def _sim_events(log_dir):
    """(name, start ns, end ns, ids) of the trace's ``sim/`` host spans,
    in start order."""
    path, = glob.glob(str(log_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
            dict(ev.stats))
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith("sim/")]
    return sorted(evs, key=lambda e: e[1])


@pytest.mark.parametrize("kind", ["run", "run_batch"])
def test_spans_of_one_call_nest_in_order_and_share_its_run_number(
        tmp_path, kind):
    sim = _tiny_sim("uniform", "none", False)
    _call(sim, kind, 10)                     # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        t0 = time.perf_counter()
        res = _call(sim, kind, 11)
        call_s = time.perf_counter() - t0
    run_no = Simulation.stats()["runs"]
    evs = _sim_events(tmp_path)
    (root, r0, r1, ids), children = evs[0], evs[1:]
    assert root == f"sim/{kind}"
    assert [c[0] for c in children] == STAGES[kind]
    assert all(r0 <= s <= e <= r1 for _, s, e, _ in children)
    assert ids == {"run": run_no, "seed": 11}
    assert all(c[3] == ids for c in children)
    assert list(res.host_s) == STAGES[kind]
    assert sum(res.host_s.values()) <= call_s
    # the record of the last call is the one the result carries
    rec = hostspans.last()
    assert (rec.name, rec.ids, rec.seconds) == (root, ids, res.host_s)


def test_a_second_seed_adds_one_cache_hit_and_no_compile():
    Simulation.reset_stats()
    assert set(Simulation.stats().values()) == {0}
    sim = _tiny_sim("uniform", "none", False)
    sim.run(seed=1)
    before = Simulation.stats()
    res = sim.run(seed=2)
    after = Simulation.stats()
    assert after["runs"] == before["runs"] + 1
    assert after["program_cache_hits"] == before["program_cache_hits"] + 1
    for k in ("program_compiles", "compile_s", "backend_compiles",
              "persistent_cache_hits"):
        assert after[k] == before[k], k
    assert res.compile_time_s == 0.0
    assert hostspans.last().compiles == {}


def test_a_new_program_counts_one_compile_inside_its_lookup():
    base = _tiny_sim("uniform", "none", False)
    # n_ticks is static: a length no other test uses compiles anew
    sim = Simulation(base.graph, caps=base.caps,
                     params=dataclasses.replace(base.params, n_ticks=9))
    before = Simulation.stats()
    res = sim.run()
    after = Simulation.stats()
    rec = hostspans.last()
    assert after["program_compiles"] == before["program_compiles"] + 1
    assert after["compile_s"] - before["compile_s"] == pytest.approx(
        res.compile_time_s)
    assert rec.compiles.get("sim/compile", 0) >= 1
    assert after["backend_compiles"] - before["backend_compiles"] == sum(
        rec.compiles.values())
    # the compile is timed inside the lookup that missed
    assert res.compile_time_s <= res.host_s["sim/lookup"]
    assert sim.last_compiled.as_text().startswith("HloModule jit_run_fn")


def test_a_failed_call_leaves_no_span_open():
    sim = _tiny_sim("uniform", "none", False)
    with pytest.raises(ValueError, match="one AppStatic per sweep point"):
        sim.run_batch([sim.params] * 2, apps=[sim.app])
    res = sim.run(seed=3)
    rec = hostspans.last()
    assert (rec.name, rec.ids["seed"]) == ("sim/run", 3)
    assert list(res.host_s) == STAGES["run"]


def test_count_backend_compiles_counts_only_inside_its_block():
    f = jax.jit(lambda x: x * 3 + 1)
    x5, x6, x7 = (jax.numpy.ones(n) for n in (5, 6, 7))
    with hostspans.count_backend_compiles() as outer:
        with hostspans.count_backend_compiles() as inner:
            f(x5).block_until_ready()
        f(x6).block_until_ready()
    f(x7).block_until_ready()
    assert (inner[0], outer[0]) == (1, 2)

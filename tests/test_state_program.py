"""``Simulation.init_state``'s one compiled program and ``run``'s cached
``DynParams``: the state equals the eager build bit for bit, can be
donated as it comes, and leaves the run program unchanged."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.layout_check import _tiny_sim
from repro.core import Simulation
from repro.core.placement import initial_allocation
from repro.core.types import zeros_state
from repro.obs import hostspans

# (network, faults, egress shaping, telemetry): the four golden combos,
# then streamed telemetry with burn-rate alerting
MODES = [("uniform", "none", False, False), ("uniform", "chaos", False, False),
         ("fabric", "none", False, False), ("fabric", "chaos", False, False),
         ("fabric", "chaos", True, "alert")]
MODE_IDS = ["uniform-none", "uniform-chaos", "fabric-none", "fabric-chaos",
            "fabric-chaos-stream-burn"]
# 1386736889 is job 0 of benchmark run seed 3141592653
# (bench/cnsbench/jobs.py:job_seed)
SEEDS = [0, 7, 2 ** 31 - 1, 1386736889]


@functools.lru_cache(maxsize=None)
def _sim(mode) -> Simulation:
    return _tiny_sim(*mode)


def _eager_state(sim: Simulation, seed: int):
    """The initial state built leaf by leaf with eager ops: zeros_state,
    then Algorithm 3's placement and the VM and host tables over it."""
    state = zeros_state(sim.caps, sim.params, jax.random.PRNGKey(seed),
                        app=sim.app)
    app = sim.app
    inst, iof, reps = initial_allocation(
        np.asarray(app.tmpl_replicas), np.asarray(app.tmpl_mips),
        np.asarray(app.tmpl_limit_mips), np.asarray(app.tmpl_ram),
        np.asarray(app.tmpl_limit_ram), np.asarray(app.tmpl_bw),
        sim.vm_mips, sim.vm_ram, sim.caps, policy=sim.placement_policy)
    used_m = np.zeros_like(sim.vm_mips)
    used_r = np.zeros_like(sim.vm_ram)
    for i in range(sim.caps.max_instances):
        v = inst["vm"][i]
        if v >= 0:
            used_m[v] += inst["mips"][i]
            used_r[v] += inst["ram"][i]
    return state._replace(
        instances=state.instances._replace(
            **{k: jnp.asarray(v) for k, v in inst.items()}),
        vms=state.vms._replace(
            mips=jnp.asarray(sim.vm_mips), ram=jnp.asarray(sim.vm_ram),
            mips_used=jnp.asarray(used_m), ram_used=jnp.asarray(used_r)),
        sched=state.sched._replace(inst_of_rank=jnp.asarray(iof),
                                   svc_replicas=jnp.asarray(reps)),
        hosts=state.hosts._replace(
            egress_scale=jnp.asarray(sim.host_egress_scale),
            ingress_scale=jnp.asarray(sim.host_ingress_scale),
            cpu_scale=jnp.asarray(sim.host_cpu_scale)))


def _assert_same_tree(got, want):
    """Same tree structure, and per leaf the same shape, dtype, weak type
    and bits."""
    g, g_def = jax.tree_util.tree_flatten(got)
    w, w_def = jax.tree_util.tree_flatten(want)
    assert g_def == w_def
    for i, (a, b) in enumerate(zip(g, w)):
        assert (a.shape, a.dtype, a.weak_type) == \
            (b.shape, b.dtype, b.weak_type), i
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), i


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mode", MODES, ids=MODE_IDS)
def test_program_state_equals_the_eager_build(mode, seed):
    sim = _sim(mode)
    _assert_same_tree(sim.init_state(seed), _eager_state(sim, seed))


def test_the_seed_key_is_the_eager_key_for_every_seed_run_accepts():
    sim = _sim(MODES[0])
    for seed in (0, 2 ** 31 - 1, 2147483999, 3141592653, 2 ** 32 + 5, -1):
        assert np.array_equal(sim.init_state(seed).rng,
                              jax.random.PRNGKey(seed)), seed


def _leaves_np(tree) -> list:
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


def test_the_state_donates_as_it_comes():
    sim = _tiny_sim("fabric", "chaos", False)
    ptrs = [x.unsafe_buffer_pointer()
            for x in jax.tree_util.tree_leaves(sim.init_state(3))]
    assert len(set(ptrs)) == len(ptrs)
    results = [sim.run(seed=k) for k in (1, 2, 3)]
    for k, res in zip((1, 2, 3), results):
        fresh = _tiny_sim("fabric", "chaos", False).run(seed=k)
        assert _leaves_np(res.state) == _leaves_np(fresh.state), k
        assert _leaves_np(res.trace) == _leaves_np(fresh.trace), k
    # run donates only the state: the cached DynParams stay alive
    (dyn,) = sim._dyn_cache.values()
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(dyn))
    assert float(dyn.dt) == pytest.approx(sim.params.dt)


def test_a_run_under_the_cpu_device_gets_cpu_state():
    sim = _tiny_sim("uniform", "none", False)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        res = sim.run(seed=4)
    devs = {d for x in jax.tree_util.tree_leaves(res.state)
            for d in x.devices()}
    assert devs == {cpu}
    assert {k[0] for k in sim._state_programs} == {"cpu:0"}
    assert {k[0] for k in sim._dyn_cache} == {"cpu:0"}


def test_one_state_compile_and_dyn_hits_across_seeds():
    sim = _tiny_sim("uniform", "chaos", False)
    Simulation.reset_stats()
    n = 4
    for k in range(n):
        sim.run(seed=100 + k)
        if k == 0:
            after_first = Simulation.stats()["backend_compiles"]
        else:
            assert hostspans.last().compiles == {}
    stats = Simulation.stats()
    assert (stats["state_programs"], stats["state_compiles"],
            stats["dyn_cache_hits"]) == (n, 1, n - 1)
    assert stats["backend_compiles"] == after_first
    # another parameter set misses the DynParams cache once, and only it
    sim.params = dataclasses.replace(sim.params, spawn_rate=20.0)
    sim.run(seed=200)
    sim.run(seed=201)
    stats = Simulation.stats()
    assert (stats["state_programs"], stats["state_compiles"],
            stats["dyn_cache_hits"]) == (n + 2, 1, n)
    assert float(sim._dyn_cache[("cpu:0", sim.params)].spawn_rate) == 20.0


def _program(text: str) -> str:
    """A compiled module's text less its source metadata (instructions'
    ``metadata={...}`` and the stack-frame tables), which name the Python
    frames it was traced from."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(.+\n)*\n?", "", text, flags=re.M)


def test_the_run_program_is_the_same_for_the_eager_state():
    sim = _tiny_sim("fabric", "none", False)
    sim.run(seed=5)
    eager = _eager_state(sim, 5)
    dyn = sim._dyn_params()
    text = (jax.jit(sim._make_run_fn(), donate_argnums=0)
            .lower(eager, dyn, sim.app).compile().as_text())
    assert _program(text) == _program(sim.last_compiled.as_text())
    # the comparison sees the inputs' types: a weak-typed clock differs
    weak = eager._replace(time=jnp.asarray(0.0))
    assert weak.time.weak_type
    text = (jax.jit(sim._make_run_fn(), donate_argnums=0)
            .lower(weak, dyn, sim.app).compile().as_text())
    assert _program(text) != _program(sim.last_compiled.as_text())

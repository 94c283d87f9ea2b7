"""Compile the main path for one described TPU v5e chip, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described and not attached, so what it would refuse on the chip (an
unsupported primitive in a kernel, a program that does not fit the
device) fails here at no chip time.  Nothing runs: a pass says nothing
about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and test workers import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.bench_capacity import CASES, build_case
from repro.core.types import DynParams
from repro.kernels.tropical.kernel import tropical_matmul_pallas

V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile_solo_run(sim, sharding):
    """The program ``Simulation.run`` compiles, lowered for ``sharding``."""
    state = jax.eval_shape(sim.init_state)
    dyn = DynParams.from_params(sim.params)
    return (jax.jit(sim._make_run_fn(), donate_argnums=0)
            .lower(_on(state, sharding), _on(dyn, sharding),
                   _on(sim.app, sharding))
            .compile())


def _case(tag, **kw):
    n_requests, n_services, replicas, _, fanout = CASES[tag]
    return build_case(n_requests, n_services, replicas, fanout, **kw)[0]


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("kw", [{}, dict(network=True, faults=True)],
                         ids=["case1b", "case1b+net+faults"])
def test_case1b_compiles_for_v5e(one_chip, kw):
    compiled = _compile_solo_run(_case("case1b", **kw), one_chip)
    assert 0 < _device_bytes(compiled) < V5E_HBM_BYTES


def test_case2b_compiles_and_fits_v5e_hbm(one_chip):
    compiled = _compile_solo_run(_case("case2b"), one_chip)
    assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("n", [128, 512])
def test_tropical_kernel_compiles_for_v5e(one_chip, n):
    x = jax.ShapeDtypeStruct((1, n, n), jnp.float32, sharding=one_chip)
    compiled = tropical_matmul_pallas.lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()

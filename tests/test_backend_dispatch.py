"""Kernel dispatch on a TPU backend, checked on the CPU.

``jax.default_backend`` is replaced inside each test, so every
``kernels/*/ops.py`` (through ``kernels/backend.py``) believes it runs
on a TPU.  There, nothing may run a kernel in interpret mode, compile a
kernel the TPU compiler refuses, or fall back to jnp without a word; the
tropical kernel, which the compiler accepts, is picked automatically.
The engine's compiled-program cache is keyed by the device, so an
executable built for one backend never serves another.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (InstanceTemplate, SimCaps, SimParams, Simulation,
                        diamond)
from repro.core.critical_path import response_times
from repro.kernels.cloudlet_step import cloudlet_finish
from repro.kernels.link_share import link_share
from repro.kernels.tropical import ops as tropical_ops
from repro.kernels.tropical import ref as tropical_ref


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sim(**kw):
    caps = SimCaps(n_clients=8, max_requests=256, max_cloudlets=256,
                   max_instances=8, n_vms=2, d_max=2, max_replicas=2)
    params = SimParams(dt=0.05, n_ticks=37, n_clients=6, spawn_rate=4.0,
                       wait_lo=0.5, wait_hi=1.5, **kw)
    return Simulation(diamond(mi=400.0), caps=caps, params=params,
                      default_template=InstanceTemplate(mips=4000.0,
                                                        limit_mips=8000.0))


def _finish_args(C=64, R=16, I=4):
    rng = np.random.default_rng(0)
    return (jnp.full((C,), 2, jnp.int32),
            jnp.asarray(rng.uniform(1, 5, C), jnp.float32),
            jnp.asarray(rng.integers(0, I, C), jnp.int32),
            jnp.asarray(rng.integers(0, R, C), jnp.int32),
            jnp.zeros((C,), jnp.float32), jnp.zeros((C,), jnp.float32),
            jnp.zeros((C,), jnp.int32), jnp.full((C,), 10.0, jnp.float32),
            1.0, 0.5, jnp.zeros((R,), jnp.float32),
            jnp.zeros((R,), jnp.int32), jnp.ones((R,), jnp.int32))


def _link_args(C=32, H=4):
    rng = np.random.default_rng(1)
    return (jnp.asarray(rng.integers(-1, H, C), jnp.int32),
            jnp.asarray(rng.integers(0, H, C), jnp.int32),
            jnp.ones((C,), bool), jnp.full((H,), 100.0, jnp.float32),
            jnp.full((H,), 100.0, jnp.float32))


@pytest.mark.parametrize("network, refused", [("uniform", "scatter-add"),
                                              ("fabric", "gather")])
def test_pallas_tick_raises_on_tpu(on_tpu, network, refused):
    sim = _sim(use_pallas_tick=True, network=network)
    with pytest.raises(NotImplementedError, match=refused):
        sim.run()


def test_refused_kernels_raise_instead_of_falling_back(on_tpu):
    """The default on a TPU (``use_pallas=None``) is the kernel; it is
    refused at any size rather than swapped for jnp."""
    with pytest.raises(NotImplementedError, match="Speed 6"):
        cloudlet_finish(*_finish_args(), n_inst=4)
    with pytest.raises(NotImplementedError, match="Speed 8"):
        link_share(*_link_args())
    # asking for jnp explicitly still works
    cloudlet_finish(*_finish_args(), n_inst=4, use_pallas=False)
    link_share(*_link_args(), use_pallas=False)


def test_interpret_mode_raises_on_tpu(on_tpu, monkeypatch):
    with pytest.raises(RuntimeError, match="interpret"):
        _sim(pallas_interpret=True).run()
    with pytest.raises(RuntimeError, match="interpret"):
        cloudlet_finish(*_finish_args(), n_inst=4, use_pallas=False,
                        interpret=True)
    x = jnp.zeros((4, 4), jnp.float32)
    with pytest.raises(RuntimeError, match="interpret"):
        tropical_ops.tropical_matmul(x, x, interpret=True)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="interpret"):
        link_share(*_link_args(), use_pallas=False)


def test_tropical_picks_the_kernel_on_tpu(on_tpu, monkeypatch):
    """On a TPU the critical-path analysis runs the compiled kernel (not
    interpret mode, not the jnp reference).  The CPU cannot run it, so
    the call is answered by the same kernel in interpret mode."""
    calls = []
    real = tropical_ops.tropical_matmul_pallas

    def spy(x, a, interpret=False):
        calls.append(interpret)
        return real(x, a, interpret=True)

    monkeypatch.setattr(tropical_ops, "tropical_matmul_pallas", spy)
    g = diamond(mi=400.0)
    delays = np.linspace(1.0, 2.0, g.n_services)
    got = response_times(g, delays)
    assert calls and not any(calls)
    a = jnp.asarray(np.where(g.adjacency(), delays[None, :].astype(np.float32),
                             np.float32(-np.inf)))
    want = tropical_ref.tropical_closure(a, depth=g.depth)
    entry = int(g.api_entry[0])
    assert got[0] == pytest.approx(
        delays[entry] + max(float(np.asarray(want)[entry].max()), 0.0))


def test_compile_cache_is_keyed_by_device(monkeypatch):
    sim = _sim(seed=5)
    monkeypatch.setattr(Simulation, "_device_key",
                        staticmethod(lambda state: "tpu:0"))
    assert sim.run().compile_time_s > 0.0       # compiled for "tpu:0"
    monkeypatch.undo()
    cpu = sim.run()
    assert cpu.compile_time_s > 0.0             # not handed the "tpu" one
    assert sim.run().compile_time_s == 0.0      # cpu:0 now hits
    dev, = cpu.state.requests.response.devices()
    assert dev.platform == "cpu"

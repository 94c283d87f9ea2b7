"""Kernel dispatch on a TPU backend, checked on the CPU.

``jax.default_backend`` is replaced inside each test, so the tropical
kernel's wrapper (``kernels/tropical/ops.py``) believes it runs on a TPU.
There it picks the compiled kernel for the critical-path analysis, and
interpret mode (``interpret=True`` or ``REPRO_PALLAS_INTERPRET=1``)
raises instead of running the kernel in the host interpreter.  The
engine's compiled-program cache is keyed by the device, so an executable
built for one backend never serves another.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (InstanceTemplate, SimCaps, SimParams, Simulation,
                        diamond)
from repro.core.critical_path import response_times
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


def test_interpret_mode_raises_on_tpu(on_tpu, monkeypatch):
    x = jnp.zeros((4, 4), jnp.float32)
    with pytest.raises(RuntimeError, match="interpret"):
        tropical_ops.tropical_matmul(x, x, interpret=True)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="interpret"):
        tropical_ops.tropical_matmul(x, x)


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

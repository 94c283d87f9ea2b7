"""Request Generator validation against the paper's closed forms (Eqs 1–4).

This is the test-suite version of §6.2 / Fig 9: the simulated client count,
QPS and cumulative request curves must match N(t), λ(t), R(t).
"""
import numpy as np
import pytest

from repro.core import (SimCaps, SimParams, Simulation, linear_chain,
                        qps_analytic, total_requests_analytic)


def _run_generator(n_clients, spawn_rate, p, n_ticks=3000, dt=0.1, seed=0):
    g = linear_chain(1, mi=1.0)  # trivial service so requests drain instantly
    caps = SimCaps(n_clients=n_clients, max_requests=200_000,
                   max_cloudlets=4096, max_instances=4, n_vms=2,
                   d_max=1, max_replicas=1)
    params = SimParams(dt=dt, n_ticks=n_ticks, n_clients=n_clients,
                       spawn_rate=spawn_rate, wait_lo=p[0], wait_hi=p[1],
                       seed=seed)
    sim = Simulation(g, caps=caps, params=params)
    res = sim.run()
    tr = res.trace_np()
    return sim, res, tr


@pytest.mark.parametrize("n_clients,v,p", [
    (100, 1.0, (4.0, 6.0)),
    (50, 2.0, (2.0, 6.0)),
])
def test_client_ramp_matches_eq1(n_clients, v, p):
    _, _, tr = _run_generator(n_clients, v, p)
    t = np.arange(len(tr["active_clients"])) * 0.1
    expect = np.minimum(n_clients, np.floor(v * t) + 1)
    got = tr["active_clients"]
    # Eq 1 with the +1 discretization of "clients activate at ramp rate v"
    assert np.abs(got - expect).max() <= 1


def test_qps_converges_to_eq3():
    n_clients, v, p = 100, 1.0, (4.0, 6.0)
    _, _, tr = _run_generator(n_clients, v, p, n_ticks=6000)
    qps = tr["generated"] / 0.1
    # steady state after ramp (Nc/v = 100 s → tick 1000); average over tail
    steady = qps[2000:].mean()
    expect = qps_analytic(np.array([1e9]), SimParams(
        n_clients=n_clients, spawn_rate=v, wait_lo=p[0], wait_hi=p[1]))[0]
    # paper Fig 9b: oscillatory convergence around 2Nc/(p0+p1) = 20
    assert abs(steady - expect) / expect < 0.08, (steady, expect)


def test_total_requests_piecewise_eq4():
    n_clients, v, p = 80, 1.0, (4.0, 6.0)
    sim, res, tr = _run_generator(n_clients, v, p, n_ticks=4000)
    t = (np.arange(len(tr["generated"])) + 1) * 0.1
    total = np.cumsum(tr["generated"])
    # Eq 4 models the renewal process; each client additionally fires
    # immediately on activation (Locust semantics), adding +N(t).
    expect = total_requests_analytic(t, sim.params) + \
        np.minimum(n_clients, np.floor(v * t) + 1)
    tail = t > 30.0
    rel = np.abs(total[tail] - expect[tail]) / np.maximum(expect[tail], 1.0)
    assert rel.mean() < 0.05, rel.mean()
    assert rel.max() < 0.15
    # curvature check: ramp segment superlinear, steady segment linear
    ramp_end = int(n_clients / v / 0.1)
    mid = total[ramp_end // 2]
    assert mid < expect[ramp_end] * 0.65  # t²/ramp² = 0.25 ≪ 0.65 at halfway


def test_num_limit_respected():
    g = linear_chain(1, mi=1.0)
    caps = SimCaps(n_clients=32, max_requests=4096, max_cloudlets=1024,
                   max_instances=4, n_vms=2, d_max=1, max_replicas=1)
    params = SimParams(dt=0.1, n_ticks=500, n_clients=32, spawn_rate=100.0,
                       wait_lo=0.2, wait_hi=0.4, num_limit=100)
    sim = Simulation(g, caps=caps, params=params)
    res = sim.run()
    assert int(res.state.requests.count) == 100



@pytest.mark.parametrize("lo,hi,dt", [(0.5, 1.5, 0.01), (5.0, 15.0, 0.1)])
def test_wait_ticks_divide_rounds_to_nearest(lo, hi, dt):
    """Every think time the generator can draw (u on its 2^-23 grid)
    divides by dt as IEEE 754 does, and a quotient one or two units in
    the last place off, as a TPU's divide can give, is moved back to it
    by ``divide_rn``'s two steps."""
    import jax
    import jax.numpy as jnp

    from repro.core.generator import _nearest, divide_rn

    f32 = np.float32
    u = np.arange(2 ** 23, dtype=f32) * f32(2.0 ** -23)
    z = jnp.asarray(f32(lo) + (f32(hi) - f32(lo)) * u)
    want = np.asarray(z) / f32(dt)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(divide_rn)(z, f32(dt))), want)
    two_steps = jax.jit(lambda x, y, q: _nearest(x, y, _nearest(x, y, q)))
    for off in (np.inf, -np.inf):
        skewed = jnp.asarray(want)
        for _ in range(2):
            skewed = jnp.nextafter(skewed, f32(off))
            np.testing.assert_array_equal(
                np.asarray(two_steps(z, f32(dt), skewed)), want)

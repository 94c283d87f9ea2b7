"""Request Generator (paper §4.3.1, Algorithm 1, Eqs 1–4).

Vectorized Locust-style closed-loop client model: ``N_c`` clients ramp up at
``v`` clients/second; each client fires a request at a weighted-random API,
then sleeps uniform ``[p0, p1]`` seconds.  The closed forms the paper derives
(Eqs 1, 3, 4) are provided as `*_analytic` functions and are asserted against
the simulated trace in tests and `benchmarks/bench_generator.py` (Fig 9).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import streams
from .types import DynParams, SimParams


class GenOut(NamedTuple):
    fired: jnp.ndarray       # [Nc] bool — client fired this tick
    api: jnp.ndarray         # [Nc] i32 — chosen API (valid where fired)
    n_active: jnp.ndarray    # scalar i32 — active clients (Eq 1)
    wait_proposal: jnp.ndarray  # [Nc] i32 — next wait if the fire is accepted


def client_phase(wait: jnp.ndarray, time: jnp.ndarray, req_count: jnp.ndarray,
                 api_weight_cdf: jnp.ndarray, dyn: DynParams,
                 rng: jnp.ndarray) -> GenOut:
    """One generation tick (paper Alg 1 lines 4–17, vectorized).

    Fire decisions + proposed wait resets; the engine commits them after
    admission (backpressure may defer a fire to the next tick).
    """
    Nc = wait.shape[0]
    idx = jnp.arange(Nc)
    # Eq 1: N(t) = min(Nc, v * t)   (ramp at spawn rate v).
    n_active = jnp.minimum(
        dyn.n_clients,
        jnp.floor(dyn.spawn_rate * time).astype(jnp.int32) + 1,
    )
    active = idx < n_active
    under_limit = req_count < dyn.num_limit
    fired = active & (wait <= 0) & under_limit

    k_api, k_wait = streams.split(rng, names=("api", "wait"))
    # Weighted API selection (Alg 1 line 9): inverse-CDF on the weight set.
    u = jax.random.uniform(k_api, (Nc,))
    api = jnp.searchsorted(api_weight_cdf, u).astype(jnp.int32)
    api = jnp.minimum(api, api_weight_cdf.shape[0] - 1)

    # Alg 1 line 13: wait ~ U[p0, p1] (converted to ticks, ≥ 1).
    wait_s = dyn.wait_lo + (dyn.wait_hi - dyn.wait_lo) \
        * jax.random.uniform(k_wait, (Nc,))
    wait_ticks = jnp.maximum(jnp.round(divide_rn(wait_s, dyn.dt)),
                             1).astype(jnp.int32)
    return GenOut(fired=fired, api=api, n_active=n_active,
                  wait_proposal=wait_ticks)


def _two_prod(a: jnp.ndarray, b: jnp.ndarray):
    """(p, e): p = a * b rounded, and p + e = a * b exactly (Dekker's
    product, with Veltkamp's split of a float32 into two 12-bit
    halves)."""
    def halves(v):
        c = v * jnp.float32(4097.0)
        hi = c - (c - v)
        return hi, v - hi

    p = a * b
    ah, al = halves(a)
    bh, bl = halves(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def divide_rn(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """``x / y`` for ``x >= 0``, ``y > 0``, rounded to nearest as IEEE 754
    divides.

    A TPU's float32 divide can come out one or two units in the last
    place off (measured on a v5e), and a quotient compared with a
    threshold (``round`` near a half, a finish test) then goes the other
    way.  The remainder ``x - q * y`` of a quotient ``q`` one unit off is
    a float, so it is computed exactly here, and it says whether a
    neighbour of ``q`` is nearer to ``x / y``; two such steps bring a
    quotient two units off home.  Where the divide rounds correctly (the
    CPU), ``q`` itself is returned."""
    q = x / y
    return _nearest(x, y, _nearest(x, y, q))


def _nearest(x: jnp.ndarray, y: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Of ``q`` and its two neighbours, the one nearest to ``x / y``
    (``q`` at most one unit in the last place off)."""
    p, e = _two_prod(q, y)
    r = (x - p) - e
    up = jnp.nextafter(q, jnp.float32(jnp.inf))
    down = jnp.nextafter(q, jnp.float32(-jnp.inf))
    return jnp.where(r > (up - q) * y * 0.5, up,
                     jnp.where(-r > (q - down) * y * 0.5, down, q))


# --------------------------------------------------------------------------
# Closed forms (paper Eqs 1, 3, 4) — used to validate the generator (Fig 9).
# --------------------------------------------------------------------------

def n_clients_analytic(t: np.ndarray, params: SimParams) -> np.ndarray:
    """Eq 1: N(t) = min(N_c, v·t)."""
    return np.minimum(params.n_clients, params.spawn_rate * np.asarray(t))


def qps_analytic(t: np.ndarray, params: SimParams) -> np.ndarray:
    """Eq 3: λ(t) = N(t) · 2/(p0+p1)."""
    return (n_clients_analytic(t, params) * 2.0
            / (params.wait_lo + params.wait_hi))


def total_requests_analytic(t: np.ndarray, params: SimParams) -> np.ndarray:
    """Eq 4: piecewise ∫λ — quadratic during ramp-up, linear afterwards."""
    t = np.asarray(t, dtype=np.float64)
    Nc, v = params.n_clients, params.spawn_rate
    psum = params.wait_lo + params.wait_hi
    t_ramp = Nc / v
    ramp = v / psum * t ** 2
    steady = 2.0 * Nc / psum * t - Nc ** 2 / (v * psum)
    return np.where(t <= t_ramp, ramp, steady)


def api_weight_cdf(weights: np.ndarray) -> jnp.ndarray:
    w = np.asarray(weights, dtype=np.float64)
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    return jnp.asarray(cdf, dtype=jnp.float32)

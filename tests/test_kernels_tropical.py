"""Pallas tropical-matmul kernel vs pure-jnp oracle (interpret mode).

Sweeps shapes (aligned + ragged via the padding wrapper) and dtypes, as
required for every kernel in this repo.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.tropical import ops, ref
from repro.kernels.tropical.kernel import tropical_matmul_pallas
from repro.kernels.tropical.ops import tropical_closure, tropical_matmul


def _rand(rng, shape, dtype, density=0.7):
    x = rng.normal(size=shape).astype(dtype) * 3.0
    mask = rng.random(size=shape) < density
    return np.where(mask, x, -np.inf).astype(dtype)


@pytest.mark.parametrize("dtype", [np.float32])
@pytest.mark.parametrize("B,M,K,N,bm,bn,bk", [
    (1, 128, 128, 128, 128, 128, 128),
    (2, 256, 128, 128, 128, 128, 128),
    (1, 128, 256, 384, 128, 128, 128),
    (3, 256, 256, 256, 128, 128, 64),
    (1, 128, 128, 128, 64, 64, 32),
])
def test_kernel_matches_ref_aligned(B, M, K, N, bm, bn, bk, dtype, rng):
    x = _rand(rng, (B, M, K), dtype)
    a = _rand(rng, (B, K, N), dtype)
    got = tropical_matmul_pallas(jnp.asarray(x), jnp.asarray(a),
                                 bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.tropical_matmul(jnp.asarray(x), jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("M,K,N", [(5, 7, 3), (130, 64, 257), (1, 1, 1),
                                   (127, 129, 128)])
def test_ops_padding_ragged_shapes(M, K, N, rng):
    x = _rand(rng, (M, K), np.float32)
    a = _rand(rng, (K, N), np.float32)
    got = tropical_matmul(jnp.asarray(x), jnp.asarray(a), use_pallas=True,
                          interpret=True)
    want = ref.tropical_matmul(jnp.asarray(x), jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_closure_longest_path_vs_numpy_dp(rng):
    n = 24
    # random DAG (upper triangular), weights on edges
    w = rng.uniform(0.1, 2.0, size=(n, n)).astype(np.float32)
    mask = np.triu(rng.random((n, n)) < 0.3, k=1)
    a = np.where(mask, w, -np.inf).astype(np.float32)
    got = np.asarray(tropical_closure(jnp.asarray(a)))
    # Floyd-Warshall-style DP oracle (longest path, DAG-safe)
    dp = np.where(np.eye(n, dtype=bool), 0.0, -np.inf)
    dp = np.maximum(dp, a)
    for k in range(n):
        dp = np.maximum(dp, dp[:, k:k + 1] + dp[k:k + 1, :])
    np.testing.assert_allclose(got, dp, rtol=1e-5)


def test_closure_interpret_kernel_path(rng):
    n = 12
    mask = np.triu(rng.random((n, n)) < 0.4, k=1)
    a = np.where(mask, rng.uniform(0.5, 1.5, (n, n)),
                 -np.inf).astype(np.float32)
    got = tropical_closure(jnp.asarray(a), use_pallas=True, interpret=True)
    want = tropical_closure(jnp.asarray(a), use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_identity_is_neutral(rng):
    x = _rand(rng, (1, 128, 128), np.float32)
    eye = ref.tropical_identity(128)[None]
    got = tropical_matmul_pallas(jnp.asarray(x), jnp.asarray(eye),
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got)[0], x[0], rtol=1e-6)


def test_interpret_env_runs_the_kernel_on_cpu(rng, monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 routes a default call (no flags) through
    the kernel in interpret mode on a CPU, and it agrees with ref."""
    calls = []
    real = ops.tropical_matmul_pallas

    def spy(x, a, interpret=False):
        calls.append(interpret)
        return real(x, a, interpret=interpret)

    monkeypatch.setattr(ops, "tropical_matmul_pallas", spy)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    x = _rand(rng, (5, 7), np.float32)
    a = _rand(rng, (7, 3), np.float32)
    got = tropical_matmul(jnp.asarray(x), jnp.asarray(a))
    assert calls == [True]
    want = ref.tropical_matmul(jnp.asarray(x), jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

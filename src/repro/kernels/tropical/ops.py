"""Jitted public wrappers for tropical (max-plus) linear algebra.

Dispatch policy: the Pallas kernel runs on TPU backends (or in interpret
mode for CPU validation, which raises on a TPU; see ``kernels/backend.py``);
every other path uses the pure-jnp oracle in ref.py.  Inputs are padded
with -inf to 128-aligned tiles so arbitrary service-graph sizes are
accepted.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import backend
from . import ref
from .kernel import tropical_matmul_pallas

_TILE = 128


def _pad_pow(x: jnp.ndarray, m_to: int, n_to: int) -> jnp.ndarray:
    pm = m_to - x.shape[-2]
    pn = n_to - x.shape[-1]
    if pm == 0 and pn == 0:
        return x
    cfg = [(0, 0)] * (x.ndim - 2) + [(0, pm), (0, pn)]
    return jnp.pad(x, cfg, constant_values=ref.NEG_INF)


def tropical_matmul(x: jnp.ndarray, a: jnp.ndarray,
                    use_pallas: bool | None = None,
                    interpret: bool = False) -> jnp.ndarray:
    """(…, N, K) ⊗ (…, K, M) with automatic kernel/ref dispatch."""
    interpret = backend.interpret_mode(interpret)
    if use_pallas is None:
        use_pallas = backend.on_tpu()
    if not use_pallas and not interpret:
        return ref.tropical_matmul(x, a)

    batch_shape = x.shape[:-2]
    M, K = x.shape[-2:]
    N = a.shape[-1]
    Mp = -(-M // _TILE) * _TILE
    Kp = -(-K // _TILE) * _TILE
    Np = -(-N // _TILE) * _TILE
    xb = _pad_pow(x.reshape((-1, M, K)), Mp, Kp)
    ab = _pad_pow(a.reshape((-1, K, N)), Kp, Np)
    out = tropical_matmul_pallas(xb, ab, interpret=interpret)
    return out[..., :M, :N].reshape(batch_shape + (M, N))


def tropical_closure(a: jnp.ndarray, depth: int | None = None,
                     use_pallas: bool | None = None,
                     interpret: bool = False) -> jnp.ndarray:
    """All-pairs longest path via ⌈log₂ depth⌉ squarings (see ref)."""
    n = a.shape[-1]
    depth = n if depth is None else max(int(depth), 1)
    m = jnp.maximum(a, ref.tropical_identity(n, a.dtype))
    for _ in range(int(np.ceil(np.log2(max(depth, 2))))):
        m = tropical_matmul(m, m, use_pallas=use_pallas, interpret=interpret)
    return m

"""Pallas TPU kernel: blocked max-plus (tropical) matmul.

The paper's critical-path analysis (Alg 2) is longest-path over the service
DAG; at fleet scale (thousands of services × batched delay snapshots) it is
matmul-shaped.  The MXU cannot help (the semiring replaces multiply-add
with add-max), so this kernel keeps the *memory* discipline of a blocked
matmul — HBM→VMEM tiles, 128-aligned, k-innermost accumulation — and does
the arithmetic on the VPU.

Grid: (B, M/bm, N/bn, K/bk), k innermost so the output tile stays resident
in VMEM across the k sweep (standard revisiting-accumulator pattern).

The x operand enters transposed, [B, K, M], so the rank-1 loop reads row
kk of both tiles through the refs (a dynamic index on the sublane axis).
Reading column kk of an [M, K] tile instead is a dynamic lane slice,
which the TPU compiler refuses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")


def _tropical_kernel(xt_ref, a_ref, o_ref, *, bk: int):
    """One (bm × bn) output tile; accumulate max over the k-grid axis."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    def body(kk, acc):
        # rank-1 max-plus update: acc = max(acc, x[:, kk] + a[kk, :])
        x_col = xt_ref[0, pl.ds(kk, 1), :].T       # [bm, 1]
        a_row = a_ref[0, pl.ds(kk, 1), :]          # [1, bn]
        return jnp.maximum(acc, x_col + a_row)

    o_ref[0] = jax.lax.fori_loop(0, bk, body, o_ref[0])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def tropical_matmul_pallas(x: jnp.ndarray, a: jnp.ndarray,
                           bm: int = 128, bn: int = 128, bk: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """Batched (B, M, K) ⊗ (B, K, N) → (B, M, N) in (max, +).

    Shapes must tile evenly (ops.py pads with -inf); tiles default to the
    128-aligned VPU lane width.  VMEM footprint per step:
    bm·bk + bk·bn + bm·bn floats ≈ 192 KiB at 128³ — well inside v5e VMEM.
    """
    B, M, K = x.shape
    B2, K2, N = a.shape
    assert B == B2 and K == K2, (x.shape, a.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"shapes {(M, N, K)} must tile by {(bm, bn, bk)}"

    grid = (B, M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_tropical_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bk, bm), lambda b, i, j, k: (b, k, i)),
            pl.BlockSpec((1, bk, bn), lambda b, i, j, k: (b, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, M, N), x.dtype),
        interpret=interpret,
    )(jnp.swapaxes(x, 1, 2), a)

"""Pallas TPU kernel: max-min fair NIC water-filling (DESIGN.md §6).

The water-filling recurrence is a *global* fixed-point: every round computes
one water level λ = min over all occupied ports, so the transfer set cannot
be streamed block-by-block — it must be VMEM-resident for the whole solve.
That fits: the active cloudlet buffer is ≤ 2¹³–2¹⁵ lanes (5 × f32/i32 ≈
160 KB at 8 K) and the per-host port tables are tiny.  The kernel therefore
runs on a single grid step with whole-array blocks and executes the exact
float program of ``ref.waterfill`` (same op order) on the loaded values —
interpret-mode tests assert bit-equality against the jnp oracle.

The Pallas TPU lowering refuses the 1-D gathers of ``ref.waterfill``
("Only 2D gather is supported"), so this kernel runs in interpret mode
only and ops.py raises when the compiled kernel is asked for (ROADMAP
Speed 8).  Arbitrary pool sizes are supported by padding the transfer
axis with inactive lanes (they never occupy a port).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref


def _link_share_kernel(src_ref, dst_ref, active_ref, cap_e_ref, cap_i_ref,
                       rate_o, *, iters: int):
    rate_o[...] = ref.waterfill(
        src_ref[...], dst_ref[...], active_ref[...] != 0,
        cap_e_ref[...], cap_i_ref[...], iters)


def _pad_to(x, n, value):
    pad = n - x.shape[0]
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,), value, x.dtype)])


@functools.partial(jax.jit, static_argnames=("iters", "bc", "interpret"))
def link_share_pallas(src, dst, active, cap_e, cap_i, iters: int = 4,
                      bc: int = 1024, interpret: bool = False):
    """Fair-share rates with the transfer axis padded to a ``bc`` multiple
    (inactive padding lanes never touch a port); returns [C] f32 rates."""
    C = src.shape[0]
    H = cap_e.shape[0]
    Cp = C + (-C % bc)
    src = _pad_to(src, Cp, -1)
    dst = _pad_to(dst, Cp, -1)
    active = _pad_to(active.astype(jnp.int32), Cp, 0)
    whole = lambda n: pl.BlockSpec((n,), lambda: (0,))
    rate = pl.pallas_call(
        functools.partial(_link_share_kernel, iters=iters),
        grid=(),
        in_specs=[whole(Cp), whole(Cp), whole(Cp), whole(H), whole(H)],
        out_specs=whole(Cp),
        out_shape=jax.ShapeDtypeStruct((Cp,), jnp.float32),
        interpret=interpret,
    )(src, dst, active, cap_e, cap_i)
    return rate[:C]

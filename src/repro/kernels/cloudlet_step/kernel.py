"""Pallas TPU kernel: fused cloudlet execution tick (paper §4.2 hot loop).

One VMEM pass over the active cloudlet buffer computes the elementwise
progress/finish chain AND every finish-time reduction the scheduler needs:
per-instance consumption + finish counts + sojourn/exec/wait sums (the
per-service statistics fall out of a tiny instance→service reduction
outside), and the per-request aggregates (max finish time, max critical
depth, outstanding count) updated in place.  The jnp path needs
five separate scatter passes for the same update (see ref.cloudlet_finish);
here the accumulator outputs are *revisited* by every grid step
(index_map → block 0) — the canonical Pallas reduction pattern; the
cloudlet axis is the grid.  The request-side outputs are seeded from their
input arrays on the first grid step, then scatter-updated per block.

Scatter note: the Pallas TPU lowering refuses the vector scatter
(`.at[].add`/`.at[].max` on a VMEM block: "Unimplemented primitive …:
scatter-add"), so this kernel runs in interpret mode only and ops.py
raises when the compiled kernel is asked for (ROADMAP Speed 6).  The
per-request arrays ride along whole, so a rewrite must also move them
out of VMEM for request pools of case1b's size.

Arbitrary pool sizes are supported: inputs are padded up to the block
multiple with free slots (status 0 never contributes) and the per-cloudlet
outputs sliced back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CL_EXEC = 2


def _cloudlet_kernel(time_ref, dt_ref, status_ref, rem_ref, inst_ref,
                     req_ref, arr_ref, start_ref, depth_ref,
                     rate_ref, reqf_in, reqc_in, reqo_in,
                     rem_o, fin_o, tfin_o, cons_o,
                     inst_o, reqf_o, reqc_o, reqo_o,
                     *, n_inst: int, n_req: int):
    c = pl.program_id(0)

    @pl.when(c == 0)
    def _init():
        inst_o[...] = jnp.zeros_like(inst_o)
        reqf_o[...] = reqf_in[...]
        reqc_o[...] = reqc_in[...]
        reqo_o[...] = reqo_in[...]

    time = time_ref[0]
    dt = dt_ref[0]
    status = status_ref[...]
    rem = rem_ref[...]
    inst = inst_ref[...]
    req = req_ref[...]
    arrival = arr_ref[...]
    start = start_ref[...]
    depth = depth_ref[...]
    rate = rate_ref[...]
    f32 = jnp.float32

    execm = status == CL_EXEC
    prog = rate * dt
    fin = execm & (rem <= prog) & (rate > 0)
    tfin = jnp.where(
        fin, jnp.clip(time + rem / jnp.maximum(rate, 1e-9), time, time + dt),
        0.0)
    consumed = jnp.where(execm, jnp.minimum(prog, rem), 0.0)
    finf = fin.astype(f32)

    rem_o[...] = jnp.where(execm, jnp.maximum(rem - prog, 0.0), rem)
    fin_o[...] = fin.astype(jnp.int32)
    tfin_o[...] = tfin
    cons_o[...] = consumed

    started = jnp.maximum(start, arrival)
    sojourn = jnp.where(fin, tfin - arrival, 0.0)
    exec_t = jnp.where(fin, tfin - started, 0.0)
    wait_t = jnp.where(fin, started - arrival, 0.0)
    iidx = jnp.where(execm & (inst >= 0), inst, n_inst)
    inst_o[...] = inst_o[...].at[iidx].add(
        jnp.stack([consumed / dt, finf, sojourn, exec_t, wait_t], axis=1),
        mode="drop")

    ridx = jnp.where(fin & (req >= 0), req, n_req)
    reqf_o[...] = reqf_o[...].at[ridx].max(tfin, mode="drop")
    reqc_o[...] = reqc_o[...].at[ridx].max(depth + 1, mode="drop")
    reqo_o[...] = reqo_o[...].at[ridx].add(-fin.astype(jnp.int32),
                                           mode="drop")


def _pad_to(x, n, value):
    pad = n - x.shape[0]
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,), value, x.dtype)])


@functools.partial(jax.jit, static_argnames=("n_inst", "bc", "interpret"))
def cloudlet_finish_pallas(status, rem, inst, req, arrival, start,
                           depth, rate, time, dt, req_finish, req_crit,
                           req_out, n_inst: int,
                           bc: int = 8192, interpret: bool = False):
    """Extended finish-reduction kernel; returns the ref.FinishOut fields
    (fin as bool) with per-cloudlet outputs sliced back to the input size."""
    C = status.shape[0]
    R = req_finish.shape[0]
    bc = min(bc, C)
    Cp = C + (-C % bc)          # pad the pool to the block multiple
    grid = (Cp // bc,)
    status = _pad_to(status, Cp, 0)          # CL_FREE: never contributes
    rem = _pad_to(rem, Cp, 0.0)
    inst = _pad_to(inst, Cp, -1)
    req = _pad_to(req, Cp, -1)
    arrival = _pad_to(arrival, Cp, 0.0)
    start = _pad_to(start, Cp, -1.0)
    depth = _pad_to(depth, Cp, 0)
    rate = _pad_to(rate, Cp, 0.0)
    time_a = jnp.asarray(time, jnp.float32).reshape(1)
    dt_a = jnp.asarray(dt, jnp.float32).reshape(1)
    blk = lambda: pl.BlockSpec((bc,), lambda c: (c,))
    acc = lambda *shape: pl.BlockSpec(shape, lambda c: (0,) * len(shape))
    f32, i32 = jnp.float32, jnp.int32
    outs = pl.pallas_call(
        functools.partial(_cloudlet_kernel, n_inst=n_inst, n_req=R),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda c: (0,)),
            pl.BlockSpec((1,), lambda c: (0,)),
            blk(), blk(), blk(), blk(), blk(), blk(), blk(), blk(),
            acc(R), acc(R), acc(R),
        ],
        out_specs=[
            blk(), blk(), blk(), blk(),
            acc(n_inst + 1, 5),                          # revisited accums
            acc(R), acc(R), acc(R),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Cp,), f32),
            jax.ShapeDtypeStruct((Cp,), i32),
            jax.ShapeDtypeStruct((Cp,), f32),
            jax.ShapeDtypeStruct((Cp,), f32),
            jax.ShapeDtypeStruct((n_inst + 1, 5), f32),
            jax.ShapeDtypeStruct((R,), f32),
            jax.ShapeDtypeStruct((R,), i32),
            jax.ShapeDtypeStruct((R,), i32),
        ],
        interpret=interpret,
    )(time_a, dt_a, status, rem, inst, req, arrival, start, depth,
      rate, req_finish, req_crit, req_out)
    new_rem, fin, tfin, cons, inst_acc, reqf, reqc, reqo = outs
    return (new_rem[:C], fin[:C].astype(bool), tfin[:C], cons[:C],
            inst_acc, reqf, reqc, reqo)


@functools.partial(jax.jit, static_argnames=("n_inst", "bc", "interpret"))
def cloudlet_step_pallas(status, rem, inst, rate, time, dt, n_inst: int,
                         bc: int = 8192, interpret: bool = False):
    """Legacy 5-output API, served by the extended kernel with inert
    service/request lanes (their accumulators are dropped)."""
    C = status.shape[0]
    neg_i = jnp.full((C,), -1, jnp.int32)
    zero_f = jnp.zeros((C,), jnp.float32)
    outs = cloudlet_finish_pallas(
        status, rem, inst, neg_i, zero_f, zero_f,
        jnp.zeros((C,), jnp.int32), rate, time, dt,
        jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.int32),
        n_inst=n_inst, bc=bc, interpret=interpret)
    new_rem, fin, tfin, cons, inst_acc = outs[:5]
    return new_rem, fin, tfin, cons, inst_acc[:n_inst, 0]

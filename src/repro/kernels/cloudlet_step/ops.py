"""Jitted wrappers for the fused cloudlet tick with backend dispatch.

The kernel runs in interpret mode only (CPU checks against ``ref``); the
TPU compiler refuses its scatter-add, so asking for the compiled kernel
raises (see ``kernels/backend.py``).  Everything else takes the jnp path.
"""
from __future__ import annotations

from .. import backend
from . import ref
from .kernel import cloudlet_finish_pallas, cloudlet_step_pallas


def _refused() -> NotImplementedError:
    return backend.refused(
        "cloudlet_finish_pallas",
        "scatter-add (the .at[].add/.max updates of VMEM blocks, "
        "kernel.py:_cloudlet_kernel)", "Speed 6")


def cloudlet_step(status, rem, inst, rate, time, dt, n_inst: int,
                  use_pallas: bool | None = None, interpret: bool = False):
    """Advance all executing cloudlets one tick (see ref.py for contract)."""
    interpret = backend.interpret_mode(interpret)
    if use_pallas is None:
        use_pallas = backend.on_tpu()
    if not interpret:
        if use_pallas:
            raise _refused()
        return ref.cloudlet_step(status, rem, inst, rate, time, dt, n_inst)
    C = status.shape[0]
    return cloudlet_step_pallas(status, rem, inst, rate, time, dt,
                                n_inst=n_inst, bc=min(8192, C),
                                interpret=interpret)


def cloudlet_finish(status, rem, inst, req, arrival, start, depth,
                    rate, time, dt, req_finish, req_crit, req_out,
                    n_inst: int,
                    use_pallas: bool | None = None, interpret: bool = False
                    ) -> ref.FinishOut:
    """One-pass execution tick + all finish reductions (engine hot path).

    Runs the extended Pallas kernel in interpret mode and the
    stacked-scatter jnp reference otherwise; ``use_pallas`` (default: on
    a TPU backend) without interpret mode raises, since the TPU compiler
    refuses the kernel.
    """
    interpret = backend.interpret_mode(interpret)
    if use_pallas is None:
        use_pallas = backend.on_tpu()
    if not interpret:
        if use_pallas:
            raise _refused()
        return ref.cloudlet_finish(status, rem, inst, req, arrival,
                                   start, depth, rate, time, dt,
                                   req_finish, req_crit, req_out,
                                   n_inst=n_inst)
    C = status.shape[0]
    outs = cloudlet_finish_pallas(status, rem, inst, req, arrival,
                                  start, depth, rate, time, dt,
                                  req_finish, req_crit, req_out,
                                  n_inst=n_inst,
                                  bc=min(8192, C), interpret=interpret)
    return ref.FinishOut(*outs)


def cloudlet_finish_pool(cl, rate, time, dt, req_finish, req_crit, req_out,
                         n_inst: int, use_pallas: bool | None = None,
                         interpret: bool = False) -> ref.FinishOut:
    """Engine-facing entry over the stacked cloudlet pool.

    The kernel's input columns are sliced out of the ``[C, NI]``/``[C, NF]``
    blocks through the mode-keyed :class:`core.types.PoolLayout` carried by
    ``cl`` — no hard-coded column positions — then dispatched exactly like
    :func:`cloudlet_finish`.  Works for any layout that registers the
    Execute-phase columns (every mode does).
    """
    L = cl.layout
    ints, flts = cl.ints, cl.flts
    return cloudlet_finish(
        ints[:, L.i("status")], flts[:, L.f("rem")], ints[:, L.i("inst")],
        ints[:, L.i("req")], flts[:, L.f("arrival")],
        flts[:, L.f("start")], ints[:, L.i("depth")], rate, time, dt,
        req_finish, req_crit, req_out, n_inst=n_inst,
        use_pallas=use_pallas, interpret=interpret)

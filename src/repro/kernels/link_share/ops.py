"""Jitted wrapper for the link_share water-filling with backend dispatch.

The kernel runs in interpret mode only (CPU checks against ``ref``); the
TPU compiler refuses its 1-D gathers, so asking for the compiled kernel
raises (see ``kernels/backend.py``).  Everything else takes the jnp path.
"""
from __future__ import annotations

from .. import backend
from . import ref
from .kernel import link_share_pallas


def link_share(src, dst, active, cap_e, cap_i, iters: int = 4,
               use_pallas: bool | None = None, interpret: bool = False):
    """Max-min fair per-transfer rates (MB/s) over host NIC ports.

    Runs the Pallas kernel in interpret mode and the jnp oracle otherwise
    (both run the identical float program); ``use_pallas`` (default: on a
    TPU backend) without interpret mode raises.
    """
    interpret = backend.interpret_mode(interpret)
    if use_pallas is None:
        use_pallas = backend.on_tpu()
    if not interpret:
        if use_pallas:
            raise backend.refused(
                "link_share_pallas",
                "1-D gathers (sat_e[src], share_e[...] in ref.waterfill, "
                "which the kernel body runs): only 2-D gathers lower",
                "Speed 8")
        return ref.link_share(src, dst, active, cap_e, cap_i, iters)
    return link_share_pallas(src, dst, active, cap_e, cap_i, iters=iters,
                             interpret=True)

"""Which path a kernel wrapper may take on the current backend.

Every ``ops.py`` asks this module instead of deciding on its own.  The
rules are the same for all of them, and none falls back in silence:

* interpret mode (``interpret=True``, ``SimParams.pallas_interpret`` or
  ``REPRO_PALLAS_INTERPRET=1``) exists to check a kernel against its jnp
  reference on a CPU.  On a TPU backend it would run the kernel in the
  host interpreter instead of on the chip, so it raises there;
* a kernel that the TPU compiler refuses raises :func:`refused` when it
  is asked for, naming the primitive, instead of compiling or quietly
  running the jnp path.
"""
from __future__ import annotations

import os

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: bool) -> bool:
    """Whether a Pallas kernel runs in interpret mode: the caller's flag
    or ``REPRO_PALLAS_INTERPRET=1``.  Raises on a TPU backend."""
    interpret = interpret or os.environ.get("REPRO_PALLAS_INTERPRET") == "1"
    if interpret and on_tpu():
        raise RuntimeError(
            "Pallas interpret mode was requested (interpret=True, "
            "SimParams.pallas_interpret or REPRO_PALLAS_INTERPRET=1) on a "
            "TPU backend: the kernel would run in the host interpreter, not "
            "on the chip. Interpret mode is for checking kernels on a CPU.")
    return interpret


def refused(kernel: str, primitive: str, roadmap_item: str
            ) -> NotImplementedError:
    """The error for a kernel that the TPU compiler does not accept."""
    return NotImplementedError(
        f"{kernel} does not compile for TPU: the Pallas TPU lowering "
        f"refuses {primitive}. Run the jnp path "
        f"(SimParams.use_pallas_tick=False); ROADMAP.md {roadmap_item} "
        "tracks the rewrite.")

"""Pallas TPU kernels with pure-jnp oracles.

Each kernel lives in its own subpackage with three files:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jitted public wrapper with backend dispatch + padding
  ref.py    — pure-jnp oracle used for interpret-mode validation

On a CPU the kernels run with ``interpret=True`` (tests sweep shapes and
dtypes against ref).  ``tropical`` serves the post-run critical-path
analysis (core/critical_path.py) and is picked on a TPU, where its
interpret mode raises; ``flash_attention`` and ``ssd_scan`` serve the
language models (models/).  No kernel runs on the simulator's tick.
"""

"""Pallas TPU kernels with pure-jnp oracles.

Each kernel lives in its own subpackage with three files:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jitted public wrapper with backend dispatch + padding
  ref.py    — pure-jnp oracle used for interpret-mode validation

On a CPU the kernels run with ``interpret=True`` (tests sweep shapes and
dtypes against ref).  backend.py holds the dispatch rules every ops.py
follows on a TPU: interpret mode raises there, and so does a kernel the
TPU compiler refuses (tests/test_v5e_compile.py compiles the ones it
accepts for a described v5e chip).
"""

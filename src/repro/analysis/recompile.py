"""Recompile sentinel — jit cache misses across sweeps must be zero.

The engine's whole performance pitch is compile-once: `Simulation`
keys its AOT cache on static knobs + pytree shapes, sweeps travel as
traced ``DynParams`` leaves, and seed changes reuse the executable (the
cache key deliberately omits the seed).  That contract silently breaks
the moment a Python scalar is closed over where a traced value belongs,
or a weak-typed constant flips an argument dtype — every sweep point
then pays a full XLA compile and an 8-point study runs 8× slower with
bit-identical results.

The sentinel counts *backend compiles* via JAX's monitoring events
(``/jax/core/compile/backend_compile_duration`` fires once per XLA
compilation, including the small eager-op kernels): a **warm pass**
runs each golden combo solo plus an 8-point ``run_batch`` sweep, then a
**counting pass** re-runs everything with different values — new seed,
perturbed sweep scalars — in identical shapes.  Any compile event in
the counting pass is a cache miss the design says cannot exist.
"""
from __future__ import annotations

import dataclasses
from typing import List

from ..obs.hostspans import count_backend_compiles


@dataclasses.dataclass
class SentinelReport:
    warm_compiles: int
    counting_compiles: int

    @property
    def problems(self) -> List[str]:
        if self.counting_compiles:
            return [
                f"recompile: {self.counting_compiles} backend compile(s) "
                "in the counting pass (warm pass compiled "
                f"{self.warm_compiles}) — some value that should be "
                "traced (DynParams leaf) or cache-keyed is being closed "
                "over as a fresh Python object per run"]
        return []


def _sweep_points(params, n_points: int = 8, offset: float = 0.0):
    return [dataclasses.replace(params,
                                spawn_rate=params.spawn_rate
                                + 0.5 * i + offset,
                                slo_ms=params.slo_ms + 10.0 * i + offset)
            for i in range(n_points)]


def run_sentinel(n_points: int = 8) -> SentinelReport:
    """Warm-then-count over the four golden combos + an 8-point sweep."""
    from .layout_check import _tiny_sim

    combos = [("uniform", "none"), ("uniform", "chaos"),
              ("fabric", "none"), ("fabric", "chaos")]

    with count_backend_compiles() as warm:
        for net, fl in combos:
            sim = _tiny_sim(net, fl, False)
            sim.run()
            sim.run_batch(_sweep_points(sim.params, n_points))

    with count_backend_compiles() as cold:
        for net, fl in combos:
            # Fresh Simulation objects: the cache must hit across
            # *instances*, not just across calls on one instance.
            sim = _tiny_sim(net, fl, False)
            sim.run(seed=sim.params.seed + 1)     # seed is not a cache key
            sim.run_batch(_sweep_points(sim.params, n_points, offset=0.25),
                          seed=sim.params.seed + 1)

    return SentinelReport(warm_compiles=warm[0], counting_compiles=cold[0])

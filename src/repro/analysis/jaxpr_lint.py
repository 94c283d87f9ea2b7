"""Jaxpr lint — walk the scan-body program for hot-loop hazards.

The fused tick's performance contract is structural: the whole run is
ONE ``lax.scan`` whose body stays f32/i32, device-resident, and
callback-free, scanned over a donated carry.  None of that is visible
in a passing test — an f64 upcast or a stray ``io_callback`` produces
the right numbers, slower.  This walker traverses the ClosedJaxpr of
the jitted run (descending through scan/cond/while/pjit sub-jaxprs) and
flags:

* ``f64`` — wide-dtype introduction (f64/i64/u64/c128 outvars, incl.
  widening ``convert_element_type``) inside the hot loop.  The engine
  is strong-typed f32/i32; wide values appear only if someone enables
  x64 and leaks a Python float through an op that then promotes.
* ``callback`` — any callback primitive (``pure_callback``,
  ``io_callback``, debug prints) inside the scan body: a host
  round-trip per tick.  Sites registered via :func:`declare_callback`
  (by host-function name) are exempt — the ONE legitimate tap is the
  telemetry exporter flush (obs/telemetry.py), which fires once per
  ring half, not per tick.
* ``transfer`` — explicit ``device_put`` transfers inside the scan
  body.
* ``donation`` — the solo run's carry is not donated (checked on the
  lowered module: every input-state buffer must carry a
  ``tf.aliasing_output`` attr / donated flag, else the pool doubles
  resident bytes).

Rules are waivable by id (``waive={"donation", ...}``) — see DESIGN.md
§8 for when that is legitimate.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Set

import jax
from jax.extend import core as jex_core

WIDE_DTYPES = ("float64", "int64", "uint64", "complex128")
RULES = ("f64", "callback", "transfer", "donation")

# Host-function names allowed to appear as callback sites in the hot
# loop.  Populated at import time by the module that OWNS the callback
# (obs/telemetry.py declares its exporter tap) — an undeclared callback
# still fails the lint, so a stray io_callback can't hide behind the
# mechanism.
_DECLARED_CALLBACKS: Set[str] = set()


def declare_callback(name: str) -> None:
    """Allow-list a callback site by its host function's ``__name__``."""
    _DECLARED_CALLBACKS.add(name)


def _callback_site(eqn) -> str:
    """Host-function name behind a callback eqn (io_callback wraps the
    target in a _FlatCallback with a ``callback_func`` attribute)."""
    cb = eqn.params.get("callback")
    fn = getattr(cb, "callback_func", cb)
    return getattr(fn, "__name__", "")


def _sub_jaxprs(eqn) -> Iterable[tuple]:
    """(jaxpr, enters_loop) pairs nested in one equation's params."""
    loop = eqn.primitive.name in ("scan", "while")
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr, loop
            elif isinstance(x, jex_core.Jaxpr):
                yield x, loop


def lint_jaxpr(jaxpr, in_loop: bool = False,
               waive: Optional[Set[str]] = None) -> List[str]:
    """Walk one (possibly closed) jaxpr; return violations.

    ``in_loop=True`` treats the given jaxpr itself as hot-loop code —
    used by tests that lint a tick body directly rather than the
    wrapping scan.
    """
    waive = waive or set()
    if hasattr(jaxpr, "jaxpr"):        # ClosedJaxpr → Jaxpr
        jaxpr = jaxpr.jaxpr
    problems: List[str] = []

    def walk(jx, hot: bool) -> None:
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if hot:
                if "f64" not in waive:
                    wide = [str(v.aval.dtype) for v in eqn.outvars
                            if getattr(v.aval, "dtype", None) is not None
                            and str(v.aval.dtype) in WIDE_DTYPES]
                    if wide:
                        problems.append(
                            f"f64: hot-loop {name!r} produces wide "
                            f"dtype(s) {wide} — the tick carry is "
                            "f32/i32; a widening here doubles scan "
                            "bandwidth")
                if "callback" not in waive \
                        and ("callback" in name or name == "debug_print") \
                        and _callback_site(eqn) not in _DECLARED_CALLBACKS:
                    problems.append(
                        f"callback: {name!r} inside the scan body — a "
                        "host round-trip every tick (declared sites: "
                        f"{sorted(_DECLARED_CALLBACKS) or 'none'})")
                if "transfer" not in waive and name == "device_put":
                    problems.append(
                        "transfer: device_put inside the scan body — "
                        "device↔host traffic in the hot loop")
            for sub, enters_loop in _sub_jaxprs(eqn):
                walk(sub, hot or enters_loop)

    walk(jaxpr, in_loop)
    return problems


def check_donation(lowered, waive: Optional[Set[str]] = None) -> List[str]:
    """Donation rule on a ``jax.jit(...).lower(...)`` result: the state
    argument (argnum 0) must be donated and XLA must have aliased at
    least one output onto it."""
    if waive and "donation" in waive:
        return []
    problems: List[str] = []
    # args_info mirrors ((args...), {kwargs}); argnum 0 is the state.
    positional = lowered.args_info[0]
    state_info = jax.tree_util.tree_leaves(positional[0])
    not_donated = sum(1 for a in state_info if not a.donated)
    if not_donated:
        problems.append(
            f"donation: {not_donated}/{len(state_info)} carry buffers "
            "not donated — pass donate_argnums=0 so the pool aliases "
            "the output instead of doubling resident bytes")
    elif "tf.aliasing_output" not in lowered.as_text():
        problems.append(
            "donation: carry marked donated but XLA aliased no output "
            "onto it (shape/dtype mismatch between input state and "
            "result?)")
    return problems


def lint_combo(network: str, faults: str,
               waive: Optional[Set[str]] = None,
               telemetry: str = "none") -> List[str]:
    """Full lint of one mode combo's solo run program (scan + donation).

    Uses the engine's own ``_make_run_fn`` so the linted program is the
    REAL one — with ``telemetry="stream"`` that is the chunked
    scan-of-scan including the declared exporter-tap io_callback (the
    allowlist mechanism is exercised, not bypassed)."""
    from repro.core.types import DynParams
    from .layout_check import _tiny_sim

    sim = _tiny_sim(network, faults, False, telemetry)
    state = sim.init_state()
    dyn = DynParams.from_params(sim.params)
    run_fn = sim._make_run_fn()

    closed = jax.make_jaxpr(run_fn)(state, dyn, sim.app)
    problems = lint_jaxpr(closed, waive=waive)
    lowered = jax.jit(run_fn, donate_argnums=0).lower(state, dyn, sim.app)
    problems += check_donation(lowered, waive=waive)
    return problems

"""Index-safety verifier — forward interval abstract interpretation of the
tick jaxpr (DESIGN.md §8).

PR 4's edge-table undersizing bug produced wrong goldens, not crashes: the
engine's gathers default to ``PROMISE_IN_BOUNDS`` (out-of-bounds is undefined
behaviour) and its scatters to ``FILL_OR_DROP`` (out-of-bounds writes vanish
silently).  This pass walks ``Simulation._tick``'s ClosedJaxpr with an
integer-interval abstract domain and *proves*, per combo:

* every ``gather`` / ``dynamic_slice`` index is in bounds for its operand;
* every ``scatter`` / ``scatter-add`` / ``scatter-min`` / ``scatter-max``
  index vector is duplicate-free — via jnp's own ``unique_indices`` flag
  (basic indexing), concrete index arrays, or the prefix-sum slot-assignment
  pattern (``cumsum`` of a 0/1 mask is strictly increasing on mask lanes) —
  unless the site sits inside a :mod:`repro.analysis.annotate` scope:
  ``repro_collide:`` (segment-sum-style accumulation, collisions intended)
  or ``repro_disjoint:`` (asserted disjoint, runtime-checked under
  ``REPRO_CHECKED=1``);
* the tick is *inductive*: output-state intervals stay inside the declared
  seeds (``types.POOL_COLUMN_BOUNDS`` + the per-leaf table below), so the
  per-tick proof extends to whole runs.

Abstract values carry, besides ``[lo, hi]``:

* per-column intervals along one axis (``cols``) — the stacked cloudlet
  blocks are one array in the jaxpr, but ``status``/``edge``/… have very
  different ranges;
* conjunction *atoms* for booleans (each comparison eqn mints an atom;
  ``and`` unions them) — used to refine ``select_n`` cases under the
  predicate, which is what sees through jnp's negative-index-wrap idiom
  (``select(idx < 0, idx + n, idx)``) without widening;
* a prefix-rank tag (``cumsum`` of an indicator: on mask lanes the values
  are pairwise distinct and ≥ ``rank_lo``) and a uniqueness tag
  (pairwise-distinct except sentinel ``filler`` values, which a
  ``FILL_OR_DROP`` scatter drops).

The interpreter inlines ``pjit``, joins ``cond`` branches, and runs
``scan``/``while`` bodies to a widened carry fixpoint.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax.extend import core as jex_core

from .annotate import COLLIDE_PREFIX, DISJOINT_PREFIX

INF = float("inf")

# --------------------------------------------------------------------------
# Abstract value
# --------------------------------------------------------------------------

_vid_counter = itertools.count(1)


@dataclasses.dataclass(frozen=True)
class IVal:
    """Interval + relational tags for one jaxpr value (array-level: the
    bounds hold for every element)."""
    lo: float
    hi: float
    # identity for refinement: select_n cases are matched to comparison
    # atoms by vid, which survives pjit in/out binding and passthroughs.
    vid: int = 0
    # bools: this value is the conjunction of these atoms (see Interp.atoms)
    atoms: frozenset = frozenset()
    # prefix-rank: on lanes where the rank_mask atoms hold, elements are
    # pairwise distinct and >= rank_lo
    rank_mask: Optional[frozenset] = None
    rank_lo: float = 0.0
    # uniqueness: elements pairwise distinct, except those inside `filler`
    unique: bool = False
    filler: Optional[Tuple[float, float]] = None
    # per-index intervals along axis `col_axis` (stacked pool blocks)
    cols: Optional[Tuple[Tuple[float, float], ...]] = None
    col_axis: Optional[int] = None
    # affine provenance: value == <vid `origin[0]`> + origin[1]; lets
    # refine() apply an atom minted on the base to a shifted copy (the
    # negative-index wrap idiom adds `n` before selecting)
    origin: Optional[Tuple[int, float]] = None
    # concrete value when statically known (index columns of `.at[:, k]`
    # writes arrive as consts/iota, not Literals)
    conc: Optional[np.ndarray] = dataclasses.field(
        default=None, compare=False, repr=False)

    def r(self, **kw) -> "IVal":
        return dataclasses.replace(self, **kw)

    @property
    def const(self) -> bool:
        return self.lo == self.hi and math.isfinite(self.lo)


def ival(lo, hi, **kw) -> IVal:
    return IVal(float(lo), float(hi), vid=next(_vid_counter), **kw)


def top_for(aval) -> IVal:
    """Dtype-top: the widest sound interval for a value of this aval."""
    dt = getattr(aval, "dtype", None)
    try:
        if dt is None:
            return ival(-INF, INF)
        if dt == jnp.bool_:
            return ival(0, 1)
        if jnp.issubdtype(dt, jnp.integer):
            info = jnp.iinfo(dt)
            return ival(info.min, info.max)
    except TypeError:  # extended dtypes (PRNG keys)
        pass
    return ival(-INF, INF)


def from_concrete(x) -> IVal:
    """Exact seed from a concrete array (consts, AppStatic tables)."""
    a = np.asarray(x)
    if a.size == 0:
        return ival(0, 0)
    if a.dtype == bool:
        a = a.astype(np.int32)
    if not np.issubdtype(a.dtype, np.number):
        return ival(-INF, INF)
    lo, hi = float(np.min(a)), float(np.max(a))
    uniq = (a.ndim == 1 and np.issubdtype(a.dtype, np.integer)
            and np.unique(a).size == a.size)
    return ival(lo, hi, unique=uniq,
                conc=a if a.size <= 65536 else None)


def join(a: IVal, b: IVal) -> IVal:
    cols = col_axis = None
    if (a.cols is not None and b.cols is not None
            and a.col_axis == b.col_axis and len(a.cols) == len(b.cols)):
        cols = tuple((min(x[0], y[0]), max(x[1], y[1]))
                     for x, y in zip(a.cols, b.cols))
        col_axis = a.col_axis
    filler = None
    unique = a.unique and b.unique and a.filler == b.filler
    if unique:
        filler = a.filler
    return ival(min(a.lo, b.lo), max(a.hi, b.hi),
                atoms=a.atoms & b.atoms, unique=unique, filler=filler,
                cols=cols, col_axis=col_axis)



def _reshape_conc(conc, shape):
    if conc is None:
        return None
    try:
        return np.asarray(conc).reshape(shape)
    except ValueError:       # stale conc from an approximating transfer
        return None

def _contained(a: IVal, b: IVal) -> bool:
    return a.lo >= b.lo and a.hi <= b.hi


def _widen(old: IVal, new: IVal, aval) -> IVal:
    """Classic interval widening against the dtype top."""
    top = top_for(aval)
    lo = old.lo if new.lo >= old.lo else top.lo
    hi = old.hi if new.hi <= old.hi else top.hi
    return ival(lo, hi)


# interval arithmetic -------------------------------------------------------

def _mx(a, b):
    """inf-safe product (0 * inf -> 0)."""
    if a == 0 or b == 0:
        return 0.0
    return a * b


def _iv_add(a, b):
    return a[0] + b[0], a[1] + b[1]


def _iv_mul(a, b):
    c = [_mx(a[0], b[0]), _mx(a[0], b[1]), _mx(a[1], b[0]), _mx(a[1], b[1])]
    return min(c), max(c)


# --------------------------------------------------------------------------
# Sites & report
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Site:
    kind: str       # gather | scatter | scatter-add | dynamic_slice | ...
    where: str      # "pool.py:111 (scatter_pool)"
    phase: str      # tick phase from the name stack ("?" before Dispatch)
    bounds: str     # in-bounds | drop | clamped | fill | OOB
    dups: str       # n/a | unique(...) | declared-collide/-disjoint | DUP
    ok: bool
    rule: str = ""  # violation rule id when not ok
    detail: str = ""

    def line(self) -> str:
        flag = "ok " if self.ok else "FAIL"
        return (f"{flag} {self.phase:>10s} {self.kind:<14s} "
                f"bounds={self.bounds:<9s} dups={self.dups:<18s} {self.where}"
                + (f"  [{self.detail}]" if self.detail else ""))


def _site_str(eqn) -> str:
    """Stable site id: 'file.py:line (function)' of the topmost repro frame."""
    try:
        from jax._src import source_info_util
        s = source_info_util.summarize(eqn.source_info)
        # '.../src/repro/core/pool.py:111:4 (scatter_pool)' → short form
        path, _, rest = s.partition(":")
        short = "/".join(path.split("/")[-1:])
        line = rest.split(":")[0]
        fn = s.partition("(")[2].rstrip(")")
        return f"{short}:{line}" + (f" ({fn})" if fn else "")
    except Exception:
        return "<unknown>"


_PHASES = ("Generation", "Disruption", "Transit", "Dispatch", "Execute",
           "Alerting", "Derive", "Response", "Scaling", "Telemetry", "Trace")


def _phase_of(scope: str) -> str:
    for part in scope.split("/"):
        if part in _PHASES:
            return part
    return "?"


# --------------------------------------------------------------------------
# The interpreter
# --------------------------------------------------------------------------

class Interp:
    def __init__(self):
        self.sites: List[Site] = []
        self.unknown: Counter = Counter()
        # atom id → (op, lhs_vid, rhs_vid, lhs IVal, rhs IVal)
        self.atoms: Dict[int, tuple] = {}
        self._atom_ids = itertools.count(1)

    # -- plumbing ----------------------------------------------------------

    def read(self, v, env) -> IVal:
        if isinstance(v, jex_core.Literal):
            return from_concrete(v.val)
        return env[v]

    def run(self, closed, invals: Sequence[IVal], scope: str = "") -> List[IVal]:
        jx = closed.jaxpr if hasattr(closed, "jaxpr") else closed
        consts = closed.consts if hasattr(closed, "consts") else []
        env: Dict = {}
        for v, c in zip(jx.constvars, consts):
            env[v] = from_concrete(c)
        for v, val in zip(jx.invars, invals):
            env[v] = val
        for eqn in jx.eqns:
            self.eqn(eqn, env, scope)
        return [self.read(v, env) for v in jx.outvars]

    def eqn(self, eqn, env, scope) -> None:
        name = eqn.primitive.name
        stack = str(eqn.source_info.name_stack)
        esc = scope + ("/" if scope and stack else "") + stack
        invals = [self.read(v, env) for v in eqn.invars]
        fn = getattr(self, "p_" + name.replace("-", "_"), None)
        if fn is None:
            self.unknown[name] += 1
            outs = [top_for(v.aval) for v in eqn.outvars]
        else:
            outs = fn(eqn, invals, esc)
        for v, val in zip(eqn.outvars, outs):
            env[v] = val

    def _tops(self, eqn):
        return [top_for(v.aval) for v in eqn.outvars]

    # -- refinement --------------------------------------------------------

    def refine(self, val: IVal, atoms: frozenset, negate: bool = False) -> IVal:
        """Tighten `val` assuming every comparison atom in `atoms` holds
        (or, with ``negate``, that the single atom is false).  The match is
        by vid, or by affine provenance: for ``val == base + off`` an atom
        on ``base`` applies with its bounds shifted by ``off``."""
        _NEG = {"lt": "ge", "le": "gt", "gt": "le", "ge": "lt"}
        if negate and len(atoms) != 1:
            return val                 # ¬(a ∧ b) is a disjunction — skip
        lo, hi = val.lo, val.hi
        for aid in atoms:
            op, lvid, rvid, liv, riv = self.atoms[aid]
            if negate:
                op = _NEG.get(op)
                if op is None:
                    continue
            for side_vid, other, flip in ((lvid, riv, False),
                                          (rvid, liv, True)):
                if val.vid == side_vid:
                    off = 0.0
                elif val.origin is not None and val.origin[0] == side_vid:
                    off = val.origin[1]
                else:
                    continue
                o = op
                if flip:  # x on the rhs of (l op x): invert the relation
                    o = {"lt": "gt", "le": "ge",
                         "gt": "lt", "ge": "le", "eq": "eq"}[op]
                if o == "lt":
                    hi = min(hi, other.hi - 1 + off)
                elif o == "le":
                    hi = min(hi, other.hi + off)
                elif o == "gt":
                    lo = max(lo, other.lo + 1 + off)
                elif o == "ge":
                    lo = max(lo, other.lo + off)
                elif o == "eq":
                    lo = max(lo, other.lo + off)
                    hi = min(hi, other.hi + off)
        if not negate and val.rank_mask and val.rank_mask <= atoms:
            lo = max(lo, val.rank_lo)
        return val.r(lo=lo, hi=hi)

    def _cmp(self, op, eqn, invals):
        a, b = invals
        lo, hi = 0, 1
        if op == "lt":
            if a.hi < b.lo:
                lo = 1
            if a.lo >= b.hi:
                hi = 0
        elif op == "le":
            if a.hi <= b.lo:
                lo = 1
            if a.lo > b.hi:
                hi = 0
        elif op == "gt":
            if a.lo > b.hi:
                lo = 1
            if a.hi <= b.lo:
                hi = 0
        elif op == "ge":
            if a.lo >= b.hi:
                lo = 1
            if a.hi < b.lo:
                hi = 0
        elif op == "eq":
            if a.const and b.const and a.lo == b.lo:
                lo = 1
            if a.hi < b.lo or a.lo > b.hi:
                hi = 0
        aid = next(self._atom_ids)
        self.atoms[aid] = (op, a.vid, b.vid, a, b)
        return [ival(lo, hi, atoms=frozenset((aid,)))]

    # -- arithmetic --------------------------------------------------------

    @staticmethod
    def _shift(val: IVal, c: float, sign: int) -> IVal:
        d = c * sign
        base = val.origin or (val.vid, 0.0)
        out = val.r(lo=val.lo + d, hi=val.hi + d, vid=next(_vid_counter),
                    atoms=frozenset(), origin=(base[0], base[1] + d),
                    conc=None if val.conc is None else val.conc + d)
        if val.rank_mask:
            out = out.r(rank_lo=val.rank_lo + d)
        if val.filler:
            out = out.r(filler=(val.filler[0] + d, val.filler[1] + d))
        if val.cols:
            out = out.r(cols=tuple((l + d, h + d) for l, h in val.cols))
        return out

    def p_add(self, eqn, invals, scope):
        a, b = invals
        if b.const:
            return [self._shift(a, b.lo, +1)]
        if a.const:
            return [self._shift(b, a.lo, +1)]
        lo, hi = _iv_add((a.lo, a.hi), (b.lo, b.hi))
        # adding the same (traced) scalar to every lane preserves pairwise
        # distinctness — both the unique tag and the prefix-rank tag
        for x, y, yv in ((a, b, eqn.invars[1]), (b, a, eqn.invars[0])):
            if getattr(yv.aval, "shape", None) == ():
                out = ival(lo, hi)
                if x.unique and x.filler is None:
                    out = out.r(unique=True)
                if x.rank_mask:
                    out = out.r(rank_mask=x.rank_mask,
                                rank_lo=x.rank_lo + y.lo)
                if out.unique or out.rank_mask:
                    return [out]
        return [ival(lo, hi)]

    def p_sub(self, eqn, invals, scope):
        a, b = invals
        if b.const:
            return [self._shift(a, b.lo, -1)]
        lo, hi = _iv_add((a.lo, a.hi), (-b.hi, -b.lo))
        if (a.unique and a.filler is None
                and getattr(eqn.invars[1].aval, "shape", None) == ()):
            return [ival(lo, hi, unique=True)]
        return [ival(lo, hi)]

    def p_mul(self, eqn, invals, scope):
        a, b = invals
        lo, hi = _iv_mul((a.lo, a.hi), (b.lo, b.hi))
        # scaling by a positive constant keeps distinctness
        for x, c in ((a, b), (b, a)):
            if c.const and c.lo > 0 and x.unique:
                f = x.filler and (x.filler[0] * c.lo, x.filler[1] * c.lo)
                return [ival(lo, hi, unique=True, filler=f)]
        return [ival(lo, hi)]

    def p_neg(self, eqn, invals, scope):
        a, = invals
        return [ival(-a.hi, -a.lo, unique=a.unique,
                     filler=a.filler and (-a.filler[1], -a.filler[0]))]

    def p_dot_general(self, eqn, invals, scope):
        # each output is a sum of n products, n the contracted extent
        a, b = invals
        (lc, _), _ = eqn.params["dimension_numbers"]
        n = int(np.prod([eqn.invars[0].aval.shape[d] for d in lc]))
        lo, hi = _iv_mul((a.lo, a.hi), (b.lo, b.hi))
        return [ival(_mx(lo, n), _mx(hi, n))]

    def p_div(self, eqn, invals, scope):
        a, b = invals
        if b.lo > 0 or b.hi < 0:
            c = []
            for x in (a.lo, a.hi):
                for y in (b.lo, b.hi):
                    c.append(0.0 if x == 0 else
                             x / y if y != 0 else math.copysign(INF, x * y))
            lo, hi = min(c), max(c)
            if jnp.issubdtype(eqn.outvars[0].aval.dtype, jnp.integer):
                # lax.div truncates toward zero
                lo = (math.floor(lo) if lo >= 0 else math.ceil(lo)) \
                    if math.isfinite(lo) else lo
                hi = (math.floor(hi) if hi >= 0 else math.ceil(hi)) \
                    if math.isfinite(hi) else hi
            return [ival(lo, hi)]
        return self._tops(eqn)

    def p_rem(self, eqn, invals, scope):
        a, b = invals
        if b.lo > 0 and math.isfinite(b.hi):
            hi = b.hi - 1 if jnp.issubdtype(
                eqn.invars[0].aval.dtype, jnp.integer) else b.hi
            if a.lo >= 0:
                return [ival(0, min(a.hi, hi))]
            return [ival(-hi, hi)]
        return self._tops(eqn)

    def p_max(self, eqn, invals, scope):
        a, b = invals
        return [ival(max(a.lo, b.lo), max(a.hi, b.hi))]

    def p_min(self, eqn, invals, scope):
        a, b = invals
        return [ival(min(a.lo, b.lo), min(a.hi, b.hi))]

    def p_clamp(self, eqn, invals, scope):
        lo_v, x, hi_v = invals
        return [ival(max(lo_v.lo, min(x.lo, hi_v.hi)),
                     min(hi_v.hi, max(x.hi, lo_v.lo)))]

    def p_floor(self, eqn, invals, scope):
        a, = invals
        return [ival(math.floor(a.lo) if math.isfinite(a.lo) else a.lo,
                     math.floor(a.hi) if math.isfinite(a.hi) else a.hi)]

    def p_ceil(self, eqn, invals, scope):
        a, = invals
        return [ival(math.ceil(a.lo) if math.isfinite(a.lo) else a.lo,
                     math.ceil(a.hi) if math.isfinite(a.hi) else a.hi)]

    def p_round(self, eqn, invals, scope):
        a, = invals
        return [ival(math.floor(a.lo) if math.isfinite(a.lo) else a.lo,
                     math.ceil(a.hi) if math.isfinite(a.hi) else a.hi)]

    def p_sign(self, eqn, invals, scope):
        return [ival(-1, 1)]

    def p_abs(self, eqn, invals, scope):
        a, = invals
        lo = 0.0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
        return [ival(lo, max(abs(a.lo), abs(a.hi)))]

    def p_exp(self, eqn, invals, scope):
        return [ival(0, INF)]

    def p_log(self, eqn, invals, scope):
        return [ival(-INF, INF)]

    def p_sqrt(self, eqn, invals, scope):
        return [ival(0, INF)]

    def p_erf_inv(self, eqn, invals, scope):
        return [ival(-INF, INF)]

    def p_nextafter(self, eqn, invals, scope):
        # nextafter(x, y) lies between x and y
        x, y = invals
        return [ival(min(x.lo, y.lo), max(x.hi, y.hi))]

    def p_is_finite(self, eqn, invals, scope):
        return [ival(0, 1)]

    def p_integer_pow(self, eqn, invals, scope):
        a, = invals
        y = eqn.params["y"]
        if y >= 0 and a.lo >= 0:
            return [ival(_mx(a.lo, a.lo) if y == 2 else 0,
                         a.hi ** y if math.isfinite(a.hi) else INF)]
        return self._tops(eqn)

    def p_shift_right_logical(self, eqn, invals, scope):
        a, b = invals
        if a.lo >= 0 and b.const and math.isfinite(a.hi):
            s = int(b.lo)
            return [ival(int(a.lo) >> s, int(a.hi) >> s)]
        if a.lo >= 0:
            return [ival(0, a.hi)]
        return self._tops(eqn)

    def p_bitcast_convert_type(self, eqn, invals, scope):
        return self._tops(eqn)

    # -- booleans ----------------------------------------------------------

    def p_lt(self, eqn, invals, scope):
        return self._cmp("lt", eqn, invals)

    def p_le(self, eqn, invals, scope):
        return self._cmp("le", eqn, invals)

    def p_le_to(self, eqn, invals, scope):
        # total-order ≤ used by sort/searchsorted lowering; plain boolean
        return self._cmp("le", eqn, invals)

    def p_lt_to(self, eqn, invals, scope):
        return self._cmp("lt", eqn, invals)

    def p_gt(self, eqn, invals, scope):
        return self._cmp("gt", eqn, invals)

    def p_ge(self, eqn, invals, scope):
        return self._cmp("ge", eqn, invals)

    def p_eq(self, eqn, invals, scope):
        return self._cmp("eq", eqn, invals)

    def p_ne(self, eqn, invals, scope):
        a, b = invals
        lo, hi = 0, 1
        if a.hi < b.lo or a.lo > b.hi:
            lo = 1
        if a.const and b.const and a.lo == b.lo:
            hi = 0
        return [ival(lo, hi)]

    def p_and(self, eqn, invals, scope):
        a, b = invals
        if eqn.outvars[0].aval.dtype == jnp.bool_:
            return [ival(min(a.lo, b.lo) if a.lo and b.lo else 0,
                         min(a.hi, b.hi), atoms=a.atoms | b.atoms)]
        if a.lo >= 0 and b.lo >= 0:
            return [ival(0, min(a.hi, b.hi))]
        return self._tops(eqn)

    def p_or(self, eqn, invals, scope):
        a, b = invals
        if eqn.outvars[0].aval.dtype == jnp.bool_:
            return [ival(max(a.lo, b.lo), max(a.hi, b.hi))]
        if a.lo >= 0 and b.lo >= 0 and math.isfinite(max(a.hi, b.hi)):
            m = int(max(a.hi, b.hi))
            return [ival(0, (1 << m.bit_length()) - 1)]
        return self._tops(eqn)

    def p_xor(self, eqn, invals, scope):
        return self.p_or(eqn, invals, scope)

    def p_not(self, eqn, invals, scope):
        a, = invals
        if eqn.outvars[0].aval.dtype == jnp.bool_:
            return [ival(1 - a.hi, 1 - a.lo)]
        return self._tops(eqn)

    def p_select_n(self, eqn, invals, scope):
        pred, *cases = invals
        if len(cases) == 2:
            c0, c1 = cases
            if pred.lo >= 1:      # always true → case1, tags intact
                return [c1]
            if pred.hi <= 0:      # always false → case0, tags intact
                return [c0]
            r1 = self.refine(c1, pred.atoms)
            r0 = self.refine(c0, pred.atoms, negate=True)
            out = join(r0, r1)
            if c0.const:
                # prefix-rank → unique-with-sentinel: where(mask∧…, rank, K)
                if c1.rank_mask and c1.rank_mask <= pred.atoms:
                    out = out.r(unique=True, filler=(c0.lo, c0.hi))
                # distinct values masked to a constant sentinel stay distinct
                elif c1.unique and c1.filler is None:
                    out = out.r(unique=True, filler=(c0.lo, c0.hi))
            return [out]
        out = cases[0]
        for c in cases[1:]:
            out = join(out, c)
        return [out]

    # -- structure ---------------------------------------------------------

    def p_convert_element_type(self, eqn, invals, scope):
        a, = invals
        src = eqn.invars[0].aval.dtype
        dst = eqn.outvars[0].aval.dtype
        if dst == jnp.bool_:
            lo = 1 if (a.lo > 0 or a.hi < 0) else 0
            hi = 0 if (a.lo == 0 and a.hi == 0) else 1
            return [ival(lo, hi)]
        lo, hi = a.lo, a.hi
        if jnp.issubdtype(dst, jnp.integer) and not jnp.issubdtype(
                src, jnp.integer) and src != jnp.bool_:
            lo = math.floor(lo) if math.isfinite(lo) else lo
            hi = math.ceil(hi) if math.isfinite(hi) else hi
            top = top_for(eqn.outvars[0].aval)
            return [ival(max(lo, top.lo), min(hi, top.hi))]
        keep_tags = (src == jnp.bool_
                     or (jnp.issubdtype(src, jnp.integer)
                         and jnp.issubdtype(dst, jnp.integer)))
        if keep_tags:
            # bool→int indicators keep their atoms so cumsum can see them
            return [a.r(lo=lo, hi=hi, vid=next(_vid_counter))]
        return [ival(lo, hi, cols=a.cols, col_axis=a.col_axis)]

    def p_iota(self, eqn, invals, scope):
        shape = eqn.params["shape"]
        dim = eqn.params["dimension"]
        n = shape[dim]
        uniq = int(np.prod(shape)) == n
        conc = None
        if int(np.prod(shape)) <= 65536:
            conc = np.broadcast_to(
                np.arange(n).reshape([n if i == dim else 1
                                      for i in range(len(shape))]), shape)
        return [ival(0, n - 1, unique=uniq, conc=conc)]

    def p_broadcast_in_dim(self, eqn, invals, scope):
        a, = invals
        shape = eqn.params["shape"]
        bdims = eqn.params["broadcast_dimensions"]
        in_shape = getattr(eqn.invars[0].aval, "shape", ())
        same_size = int(np.prod(shape)) == int(np.prod(in_shape))
        conc = None
        if a.conc is not None and int(np.prod(shape)) <= 65536:
            try:
                tmp = [1] * len(shape)
                for i, d in enumerate(bdims):
                    tmp[d] = in_shape[i]
                conc = np.broadcast_to(np.asarray(a.conc).reshape(tmp), shape)
            except ValueError:   # stale conc from an approximating transfer
                conc = None
        out = ival(a.lo, a.hi, unique=a.unique and same_size,
                   filler=a.filler if same_size else None, conc=conc)
        if (a.cols is not None and a.col_axis is not None
                and a.col_axis < len(bdims)
                and shape[bdims[a.col_axis]] == len(a.cols)):
            out = out.r(cols=a.cols, col_axis=bdims[a.col_axis])
        if same_size:
            # element order and count preserved → positional tags survive
            out = out.r(atoms=a.atoms, rank_mask=a.rank_mask,
                        rank_lo=a.rank_lo, vid=a.vid)
        return [out]

    def p_reshape(self, eqn, invals, scope):
        a, = invals
        old = tuple(getattr(eqn.invars[0].aval, "shape", ()))
        new = tuple(eqn.params["new_sizes"])
        # flat element order and count preserved → positional tags survive
        out = a.r(cols=None, col_axis=None, conc=_reshape_conc(a.conc, new))
        # keep cols across pure rank-extension: [..] → [.., 1] etc.
        def _core(s):
            return tuple(d for d in s if d != 1)
        if a.cols is not None and _core(old) == _core(new):
            core_pos = [i for i, d in enumerate(old) if d != 1]
            if a.col_axis in core_pos:
                k = core_pos.index(a.col_axis)
                new_pos = [i for i, d in enumerate(new) if d != 1]
                out = out.r(cols=a.cols, col_axis=new_pos[k])
            elif old == new:
                out = out.r(cols=a.cols, col_axis=a.col_axis)
        return [out]

    def p_squeeze(self, eqn, invals, scope):
        a, = invals
        dims = eqn.params["dimensions"]
        out = a.r(cols=None, col_axis=None,
                  conc=_reshape_conc(a.conc, eqn.outvars[0].aval.shape))
        if a.cols is not None and a.col_axis not in dims:
            shift = sum(1 for d in dims if d < a.col_axis)
            out = out.r(cols=a.cols, col_axis=a.col_axis - shift)
        return [out]

    def p_expand_dims(self, eqn, invals, scope):
        a, = invals
        dims = eqn.params["dimensions"]
        out = a.r(cols=None, col_axis=None,
                  conc=_reshape_conc(a.conc, eqn.outvars[0].aval.shape))
        if a.cols is not None:
            shift = sum(1 for d in dims if d <= a.col_axis)
            out = out.r(cols=a.cols, col_axis=a.col_axis + shift)
        return [out]

    def p_transpose(self, eqn, invals, scope):
        a, = invals
        perm = eqn.params["permutation"]
        out = ival(a.lo, a.hi, unique=a.unique, filler=a.filler)
        if a.cols is not None:
            out = out.r(cols=a.cols, col_axis=list(perm).index(a.col_axis))
        return [out]

    def p_rev(self, eqn, invals, scope):
        a, = invals
        return [ival(a.lo, a.hi, unique=a.unique, filler=a.filler)]

    def p_slice(self, eqn, invals, scope):
        a, = invals
        # subset of pairwise-distinct stays distinct; atoms survive (a
        # shifted slice can never align with its unsliced source in a
        # same-shape select, so refinement by vid stays sound); the
        # positional rank tag does not.
        out = ival(a.lo, a.hi, unique=a.unique, filler=a.filler,
                   atoms=a.atoms)
        if a.cols is not None:
            st = eqn.params["start_indices"][a.col_axis]
            li = eqn.params["limit_indices"][a.col_axis]
            strides = eqn.params["strides"]
            step = strides[a.col_axis] if strides else 1
            sub = a.cols[st:li:step]
            if len(sub) == 1:
                out = out.r(lo=sub[0][0], hi=sub[0][1])
            else:
                out = out.r(cols=sub, col_axis=a.col_axis,
                            lo=min(c[0] for c in sub),
                            hi=max(c[1] for c in sub))
        return [out]

    def p_concatenate(self, eqn, invals, scope):
        dim = eqn.params["dimension"]
        lo = min(v.lo for v in invals)
        hi = max(v.hi for v in invals)
        # per-column tracking when concatenating along the column axis
        cols: Optional[list] = []
        for v, var in zip(invals, eqn.invars):
            shape = getattr(var.aval, "shape", ())
            if v.cols is not None and v.col_axis == dim:
                cols.extend(v.cols)
            elif dim < len(shape):
                cols.extend([(v.lo, v.hi)] * shape[dim])
            else:
                cols = None
                break
        if cols is not None and len(cols) > 64:
            cols = None   # don't track huge axes
        return [ival(lo, hi,
                     cols=tuple(cols) if cols else None,
                     col_axis=dim if cols else None)]

    def p_pad(self, eqn, invals, scope):
        a, pad_val = invals
        return [ival(min(a.lo, pad_val.lo), max(a.hi, pad_val.hi))]

    def p_sort(self, eqn, invals, scope):
        return [ival(v.lo, v.hi, unique=v.unique, filler=v.filler)
                for v in invals]

    # -- reductions --------------------------------------------------------

    def _red_n(self, eqn):
        axes = eqn.params["axes"]
        shape = getattr(eqn.invars[0].aval, "shape", ())
        return int(np.prod([shape[a] for a in axes])) if shape else 1

    def p_reduce_sum(self, eqn, invals, scope):
        a, = invals
        n = self._red_n(eqn)
        return [ival(_mx(n, a.lo), _mx(n, a.hi))]

    def p_reduce_max(self, eqn, invals, scope):
        a, = invals
        return [ival(a.lo, a.hi)]

    def p_reduce_min(self, eqn, invals, scope):
        a, = invals
        return [ival(a.lo, a.hi)]

    def p_reduce_or(self, eqn, invals, scope):
        return [ival(0, 1)]

    def p_reduce_and(self, eqn, invals, scope):
        return [ival(0, 1)]

    def p_argmax(self, eqn, invals, scope):
        axes = eqn.params["axes"]
        shape = eqn.invars[0].aval.shape
        return [ival(0, shape[axes[0]] - 1)]

    def p_argmin(self, eqn, invals, scope):
        return self.p_argmax(eqn, invals, scope)

    def p_cumsum(self, eqn, invals, scope):
        a, = invals
        shape = eqn.invars[0].aval.shape
        n = shape[eqn.params["axis"]]
        lo = min(a.lo, _mx(n, a.lo))
        hi = max(a.hi, _mx(n, a.hi))
        out = ival(lo, hi)
        # prefix-rank: inclusive cumsum of a 0/1 indicator is strictly
        # increasing (hence pairwise distinct) and >= 1 on indicator lanes
        if (len(shape) == 1 and not eqn.params.get("reverse", False)
                and a.lo >= 0 and a.hi <= 1 and a.atoms):
            out = out.r(rank_mask=a.atoms, rank_lo=1.0)
        return [out]

    def p_cummax(self, eqn, invals, scope):
        a, = invals
        return [ival(a.lo, a.hi)]

    def p_cummin(self, eqn, invals, scope):
        a, = invals
        return [ival(a.lo, a.hi)]

    # -- RNG ---------------------------------------------------------------

    def p_random_bits(self, eqn, invals, scope):
        return self._tops(eqn)

    def p_random_wrap(self, eqn, invals, scope):
        return self._tops(eqn)

    def p_random_unwrap(self, eqn, invals, scope):
        return self._tops(eqn)

    def p_random_seed(self, eqn, invals, scope):
        return self._tops(eqn)

    def p_random_split(self, eqn, invals, scope):
        return self._tops(eqn)

    def p_random_fold_in(self, eqn, invals, scope):
        return self._tops(eqn)

    def p_random_gamma(self, eqn, invals, scope):
        return [ival(0, INF)]

    def p_threefry2x32(self, eqn, invals, scope):
        return self._tops(eqn)

    # -- control flow ------------------------------------------------------

    def p_pjit(self, eqn, invals, scope):
        return self.run(eqn.params["jaxpr"], invals, scope)

    p_jit = p_pjit       # the same call primitive, renamed in newer JAX

    def p_custom_jvp_call(self, eqn, invals, scope):
        return self.run(eqn.params["call_jaxpr"], invals, scope)

    def p_custom_vjp_call(self, eqn, invals, scope):
        return self.run(eqn.params["call_jaxpr"], invals, scope)

    def p_remat(self, eqn, invals, scope):
        return self.run(eqn.params["jaxpr"], invals, scope)

    def p_cond(self, eqn, invals, scope):
        pred, *ops = invals
        branches = eqn.params["branches"]
        if pred.const and 0 <= int(pred.lo) < len(branches):
            return self.run(branches[int(pred.lo)], ops, scope)
        outs = None
        for br in branches:
            o = self.run(br, ops, scope)
            outs = o if outs is None else [join(x, y) for x, y in zip(outs, o)]
        return outs

    @staticmethod
    def _strip_leading(v: IVal) -> IVal:
        out = ival(v.lo, v.hi)
        if v.cols is not None and v.col_axis is not None and v.col_axis >= 1:
            out = out.r(cols=v.cols, col_axis=v.col_axis - 1)
        return out

    @staticmethod
    def _delta(old: float, new: float) -> float:
        """Growth of one interval endpoint across one body run (0 when the
        endpoint is already infinite)."""
        if math.isinf(old):
            return 0.0
        d = new - old
        return d if math.isfinite(d) else math.copysign(INF, d)

    def p_scan(self, eqn, invals, scope):
        """Bounded-trip widening: a scan runs its body exactly `length`
        times, so carries that grow by at most [dlo, dhi] per iteration
        are bounded by init + length·[dlo, dhi].  The growth rate observed
        on the first run is re-verified at the widened state (a carry that
        accelerates falls back to dtype-top).  Only the final, widened body
        run records sites — fixpoint iterations see transient bounds."""
        body = eqn.params["jaxpr"]
        nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
        L = int(eqn.params["length"])
        consts, carry0 = list(invals[:nc]), list(invals[nc:nc + ncar])
        xs = [self._strip_leading(v) for v in invals[nc + ncar:]]
        carry_avals = [v.aval for v in eqn.invars[nc:nc + ncar]]

        mark = len(self.sites)
        first = self.run(body, consts + carry0 + xs, scope)
        dlo = [min(0.0, self._delta(c.lo, n.lo))
               for c, n in zip(carry0, first[:ncar])]
        dhi = [max(0.0, self._delta(c.hi, n.hi))
               for c, n in zip(carry0, first[:ncar])]

        # per-column deltas for carries with stacked-pool column tracking —
        # the global delta does not bound an individual column's growth
        def _colled(c, n):
            return (c.cols is not None and n is not None
                    and n.cols is not None and c.col_axis == n.col_axis
                    and len(c.cols) == len(n.cols))

        cdel = {}                  # carry j -> ([dlo per col], [dhi per col])
        for j, (c, n) in enumerate(zip(carry0, first[:ncar])):
            if _colled(c, n):
                cdel[j] = (
                    [min(0.0, self._delta(cc[0], nc[0]))
                     for cc, nc in zip(c.cols, n.cols)],
                    [max(0.0, self._delta(cc[1], nc[1]))
                     for cc, nc in zip(c.cols, n.cols)])

        def _wcar(j, c, av, trips):
            top = top_for(av)
            out = ival(max(top.lo, c.lo + _mx(trips, dlo[j])),
                       min(top.hi, c.hi + _mx(trips, dhi[j])))
            if j in cdel:
                clo, chi = cdel[j]
                out = out.r(cols=tuple(
                    (max(top.lo, cc[0] + _mx(trips, lo_d)),
                     min(top.hi, cc[1] + _mx(trips, hi_d)))
                    for cc, lo_d, hi_d in zip(c.cols, clo, chi)),
                    col_axis=c.col_axis)
            return out

        outs = first
        for _ in range(4):
            w_in = [_wcar(j, c, av, L - 1)
                    for j, (c, av) in enumerate(zip(carry0, carry_avals))]
            del self.sites[mark:]
            outs = self.run(body, consts + w_in + xs, scope)
            ok = True
            for j, (w, n) in enumerate(zip(w_in, outs[:ncar])):
                if self._delta(w.lo, n.lo) < dlo[j] - 1e-9:
                    dlo[j] = min(dlo[j], self._delta(w.lo, n.lo))
                    ok = False
                if self._delta(w.hi, n.hi) > dhi[j] + 1e-9:
                    dhi[j] = max(dhi[j], self._delta(w.hi, n.hi))
                    ok = False
                if j in cdel:
                    if not _colled(w, n):
                        del cdel[j]          # body dropped cols — stop there
                        continue
                    clo, chi = cdel[j]
                    for k, (wc, nc) in enumerate(zip(w.cols, n.cols)):
                        if self._delta(wc[0], nc[0]) < clo[k] - 1e-9:
                            clo[k] = min(clo[k], self._delta(wc[0], nc[0]))
                            ok = False
                        if self._delta(wc[1], nc[1]) > chi[k] + 1e-9:
                            chi[k] = max(chi[k], self._delta(wc[1], nc[1]))
                            ok = False
            if ok:
                break
        else:
            # growth keeps accelerating → classic widening to dtype-top
            w_in = [top_for(av) for av in carry_avals]
            del self.sites[mark:]
            outs = self.run(body, consts + w_in + xs, scope)
            return [join(c, n) for c, n in zip(w_in, outs[:ncar])] \
                + [ival(v.lo, v.hi) for v in outs[ncar:]]

        carry_out = [_wcar(j, c, av, L)
                     for j, (c, av) in enumerate(zip(carry0, carry_avals))]
        return carry_out + [ival(v.lo, v.hi) for v in outs[ncar:]]

    def p_while(self, eqn, invals, scope):
        cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
        cond_consts = invals[:cn]
        body_consts = invals[cn:cn + bn]
        carry = list(invals[cn + bn:])
        carry_avals = [v.aval for v in eqn.invars[cn + bn:]]
        mark = len(self.sites)
        for it in range(8):
            del self.sites[mark:]
            self.run(eqn.params["cond_jaxpr"], cond_consts + carry, scope)
            new_carry = self.run(eqn.params["body_jaxpr"],
                                 body_consts + carry, scope)
            if all(_contained(n, c) for n, c in zip(new_carry, carry)):
                break
            if it < 2:
                carry = [join(c, n) for c, n in zip(carry, new_carry)]
            else:
                carry = [_widen(c, n, av)
                         for c, n, av in zip(carry, new_carry, carry_avals)]
        return carry

    # -- indexed access: the sites we verify -------------------------------

    def _index_components(self, idx_val: IVal, idx_aval, n_comp: int):
        """Per-component intervals of a [..., n_comp] index array."""
        if n_comp == 1:
            return [(idx_val.lo, idx_val.hi)]
        if (idx_val.cols is not None
                and idx_val.col_axis == len(idx_aval.shape) - 1
                and len(idx_val.cols) == n_comp):
            return list(idx_val.cols)
        return [(idx_val.lo, idx_val.hi)] * n_comp

    def _concrete(self, var):
        if isinstance(var, jex_core.Literal):
            return np.asarray(var.val)
        return None

    def p_gather(self, eqn, invals, scope):
        op, idx = invals
        dnums = eqn.params["dimension_numbers"]
        slice_sizes = eqn.params["slice_sizes"]
        mode = str(eqn.params.get("mode", ""))
        op_shape = eqn.invars[0].aval.shape
        comps = self._index_components(idx, eqn.invars[1].aval,
                                       len(dnums.start_index_map))
        proven = True
        detail = []
        for j, d in enumerate(dnums.start_index_map):
            lim = op_shape[d] - slice_sizes[d]
            lo, hi = comps[j]
            if not (lo >= 0 and hi <= lim):
                proven = False
                detail.append(f"dim{d}: [{lo:g},{hi:g}] vs [0,{lim}]")
        if proven:
            bounds, ok, rule = "in-bounds", True, ""
        elif "CLIP" in mode:
            bounds, ok, rule = "clamped", True, ""
        elif "FILL" in mode:
            bounds, ok, rule = "fill", True, ""
        else:   # PROMISE_IN_BOUNDS: out of bounds is UB
            bounds, ok, rule = "OOB", False, "oob-gather"
        self.sites.append(Site("gather", _site_str(eqn), _phase_of(scope),
                               bounds, "n/a", ok, rule, "; ".join(detail)))
        out = ival(op.lo, op.hi)
        if not proven and "FILL" in mode:
            out = join(out, top_for(eqn.outvars[0].aval))
        # row-gathers of a column-stacked operand keep per-column intervals
        if (op.cols is not None and op.col_axis is not None
                and op.col_axis not in dnums.start_index_map
                and op.col_axis not in dnums.collapsed_slice_dims
                and slice_sizes[op.col_axis] == len(op.cols)):
            kept = [d for d in range(len(op_shape))
                    if d not in dnums.collapsed_slice_dims]
            if op.col_axis in kept:
                out_axis = dnums.offset_dims[kept.index(op.col_axis)]
                out = out.r(cols=op.cols, col_axis=out_axis)
        return [out]

    def p_dynamic_slice(self, eqn, invals, scope):
        op, *starts = invals
        slice_sizes = eqn.params["slice_sizes"]
        op_shape = eqn.invars[0].aval.shape
        proven = True
        detail = []
        for d, s in enumerate(starts):
            lim = op_shape[d] - slice_sizes[d]
            if not (s.lo >= 0 and s.hi <= lim):
                proven = False
                detail.append(f"dim{d}: [{s.lo:g},{s.hi:g}] vs [0,{lim}]")
        # XLA clamps dynamic_slice starts, so memory safety is structural —
        # but a clamped start reads the wrong window; require the proof.
        bounds = "in-bounds" if proven else "OOB"
        rule = "" if proven else "oob-dslice"
        self.sites.append(Site("dynamic_slice", _site_str(eqn),
                               _phase_of(scope), bounds, "n/a", proven, rule,
                               "; ".join(detail)))
        out = ival(op.lo, op.hi, unique=op.unique, filler=op.filler)
        if (op.cols is not None and op.col_axis is not None
                and slice_sizes[op.col_axis] == len(op.cols)):
            out = out.r(cols=op.cols, col_axis=op.col_axis)
        return [out]

    def p_dynamic_update_slice(self, eqn, invals, scope):
        op, upd, *starts = invals
        op_shape = eqn.invars[0].aval.shape
        upd_shape = eqn.invars[1].aval.shape
        proven = True
        detail = []
        for d, s in enumerate(starts):
            lim = op_shape[d] - upd_shape[d]
            if not (s.lo >= 0 and s.hi <= lim):
                proven = False
                detail.append(f"dim{d}: [{s.lo:g},{s.hi:g}] vs [0,{lim}]")
        bounds = "in-bounds" if proven else "OOB"
        rule = "" if proven else "oob-dslice"
        self.sites.append(Site("dyn_update_slice", _site_str(eqn),
                               _phase_of(scope), bounds, "n/a", proven, rule,
                               "; ".join(detail)))
        out = ival(min(op.lo, upd.lo), max(op.hi, upd.hi))
        if (op.cols is not None and op.col_axis is not None
                and upd_shape[op.col_axis] == op_shape[op.col_axis]):
            ucols = (upd.cols if upd.cols is not None
                     and upd.col_axis == op.col_axis
                     and len(upd.cols) == len(op.cols)
                     else [(upd.lo, upd.hi)] * len(op.cols))
            out = out.r(cols=tuple(
                (min(a[0], b[0]), max(a[1], b[1]))
                for a, b in zip(op.cols, ucols)), col_axis=op.col_axis)
        return [out]

    def _scatter(self, eqn, invals, scope, kind):
        op, idx, upd = invals
        dnums = eqn.params["dimension_numbers"]
        mode = str(eqn.params.get("mode", ""))
        uniq_flag = eqn.params.get("unique_indices", False)
        op_shape = eqn.invars[0].aval.shape
        upd_shape = eqn.invars[2].aval.shape
        sdod = dnums.scatter_dims_to_operand_dims
        # window size along each indexed operand dim
        kept = [d for d in range(len(op_shape))
                if d not in dnums.inserted_window_dims]
        win = {d: 1 for d in range(len(op_shape))}
        for k, d in enumerate(kept):
            win[d] = upd_shape[dnums.update_window_dims[k]] \
                if k < len(dnums.update_window_dims) else 1
        comps = self._index_components(idx, eqn.invars[1].aval, len(sdod))
        proven = True
        detail = []
        lims = []
        for j, d in enumerate(sdod):
            lim = op_shape[d] - win[d]
            lims.append(lim)
            lo, hi = comps[j]
            if not (lo >= 0 and hi <= lim):
                proven = False
                detail.append(f"dim{d}: [{lo:g},{hi:g}] vs [0,{lim}]")
        drop = "FILL_OR_DROP" in mode
        if proven:
            bounds, b_ok = "in-bounds", True
        elif drop:
            bounds, b_ok = "drop", True      # OOB writes are dropped
        elif "CLIP" in mode:
            bounds, b_ok = "OOB", False      # clamped into the WRONG slot
        else:
            bounds, b_ok = "OOB", False
        # --- duplicate-freedom ---
        conc = idx.conc if idx.conc is not None \
            else self._concrete(eqn.invars[1])
        idx_size = int(np.prod(getattr(eqn.invars[1].aval, "shape", ())))
        n_rows = idx_size // len(sdod) if sdod else 0
        rows_uniq = None
        if conc is not None:
            rows = np.asarray(conc).reshape(-1, len(sdod))
            inb = np.all((rows >= 0) & (rows <= np.asarray(lims)), axis=1)
            live = rows[inb] if drop else rows
            rows_uniq = np.unique(live, axis=0).shape[0] == live.shape[0]
        dups, d_ok = "DUP", False
        if uniq_flag:
            dups, d_ok = "unique(jnp)", True
        elif n_rows == 1:
            dups, d_ok = "unique(single)", True
        elif rows_uniq:
            dups, d_ok = "unique(const)", True
        elif (len(sdod) == 1 and idx.unique
              and (idx.filler is None
                   or (drop and (idx.filler[0] > lims[0]
                                 or idx.filler[1] < 0)))):
            dups, d_ok = "unique(proven)", True
        elif COLLIDE_PREFIX in scope:
            dups, d_ok = "declared-collide", True
        elif DISJOINT_PREFIX in scope:
            dups, d_ok = "declared-disjoint", True
        ok = b_ok and d_ok
        rule = "" if ok else ("oob-scatter" if not b_ok else "dup-scatter")
        self.sites.append(Site(kind, _site_str(eqn), _phase_of(scope),
                               bounds, dups, ok, rule, "; ".join(detail)))
        # synthesize operand columns for a column-less accumulator (e.g.
        # a fresh jnp.zeros) when the update block tracks per-column
        # intervals along a window dim — the stacked [n, 5] stats tables
        if (op.cols is None and upd.cols is not None and len(sdod) == 1
                and sdod[0] != 1 and len(op_shape) == 2
                and len(upd.cols) == op_shape[1]):
            op = op.r(cols=((op.lo, op.hi),) * op_shape[1], col_axis=1)
        # --- result value ---
        if kind == "scatter":
            out = ival(min(op.lo, upd.lo), max(op.hi, upd.hi))
        elif kind == "scatter-add":
            lo = op.lo if upd.lo >= 0 else -INF
            hi = op.hi if upd.hi <= 0 else INF
            if d_ok and dups.startswith("unique"):
                lo = op.lo + min(0.0, upd.lo)
                hi = op.hi + max(0.0, upd.hi)
            out = ival(lo, hi)
        elif kind == "scatter-min":
            out = ival(min(op.lo, upd.lo), op.hi)
        elif kind == "scatter-max":
            out = ival(op.lo, max(op.hi, upd.hi))
        else:
            out = join(ival(op.lo, op.hi), top_for(eqn.outvars[0].aval))
        if op.cols is not None and op.col_axis is not None \
                and tuple(sdod) == (op.col_axis,) and conc is not None \
                and conc.size <= 8:
            # constant column id(s): only the named columns change — this is
            # the ``with_cols`` write path (``ints.at[:, k].set(v)``).
            full = all(win[d] == op_shape[d]
                       for d in range(len(op_shape)) if d != op.col_axis)
            cols = list(op.cols)
            for k in np.asarray(conc).ravel().tolist():
                k = int(k)
                if not (0 <= k < len(cols)):
                    continue
                old = cols[k]
                if kind == "scatter" and full and not drop:
                    cols[k] = (upd.lo, upd.hi)
                elif kind == "scatter":
                    cols[k] = (min(old[0], upd.lo), max(old[1], upd.hi))
                elif kind == "scatter-add":
                    cols[k] = (old[0] if upd.lo >= 0 else -INF,
                               old[1] if upd.hi <= 0 else INF)
                elif kind == "scatter-min":
                    cols[k] = (min(old[0], upd.lo), old[1])
                elif kind == "scatter-max":
                    cols[k] = (old[0], max(old[1], upd.hi))
                else:
                    cols[k] = (min(old[0], upd.lo), max(old[1], upd.hi))
            cols = tuple(cols)
            out = out.r(cols=cols, col_axis=op.col_axis,
                        lo=min(c[0] for c in cols),
                        hi=max(c[1] for c in cols))
        elif op.cols is not None and op.col_axis is not None \
                and op.col_axis not in sdod:
            # per-column union with the update block
            k = (kept.index(op.col_axis) if op.col_axis in kept else None)
            uax = (dnums.update_window_dims[k]
                   if k is not None and k < len(dnums.update_window_dims)
                   else None)
            ucols = (upd.cols if upd.cols is not None and uax is not None
                     and upd.col_axis == uax and len(upd.cols) == len(op.cols)
                     else [(upd.lo, upd.hi)] * len(op.cols))
            if kind == "scatter":
                cols = tuple((min(a[0], b[0]), max(a[1], b[1]))
                             for a, b in zip(op.cols, ucols))
            elif kind == "scatter-add":
                cols = tuple(
                    (a[0] if b[0] >= 0 else -INF, a[1] if b[1] <= 0 else INF)
                    for a, b in zip(op.cols, ucols))
            else:
                cols = tuple((min(a[0], b[0]), max(a[1], b[1]))
                             for a, b in zip(op.cols, ucols))
            out = out.r(cols=cols, col_axis=op.col_axis,
                        lo=min(out.lo, min(c[0] for c in cols)),
                        hi=max(out.hi, max(c[1] for c in cols)))
        return [out]

    def p_scatter(self, eqn, invals, scope):
        return self._scatter(eqn, invals, scope, "scatter")

    def p_scatter_add(self, eqn, invals, scope):
        return self._scatter(eqn, invals, scope, "scatter-add")

    def p_scatter_min(self, eqn, invals, scope):
        return self._scatter(eqn, invals, scope, "scatter-min")

    def p_scatter_max(self, eqn, invals, scope):
        return self._scatter(eqn, invals, scope, "scatter-max")

    def p_scatter_mul(self, eqn, invals, scope):
        return self._scatter(eqn, invals, scope, "scatter-mul")


# --------------------------------------------------------------------------
# Seeding: declared inductive bounds per state leaf
# --------------------------------------------------------------------------

def _state_bound_rules(caps, app):
    """path-suffix → (lo, hi) for int leaves whose range matters.  Floats
    and counters default to dtype-top / [0, inf) and are listed only when
    they feed an index computation."""
    from repro.core.types import CL_TRANSIT, INST_DOWN, edge_table_size
    S = app.n_services
    H = app.n_hosts
    A = app.n_apis
    E = edge_table_size(S, caps.d_max, A)
    return {
        ".tick": (0, INF),
        ".time": (0, INF),
        ".rr": (0, caps.max_replicas - 1),
        ".clients.wait": (0, INF),
        ".requests.count": (0, INF),
        ".requests.api": (-1, A - 1),
        ".requests.outstanding": (-INF, INF),
        ".requests.spawned": (0, INF),
        ".requests.critical_len": (0, INF),
        ".instances.status": (0, INST_DOWN),
        ".instances.service": (-1, S - 1),
        ".instances.vm": (-1, caps.n_vms - 1),
        ".instances.host": (-1, H - 1),
        ".instances.n_exec": (-INF, INF),
        ".instances.busy_ticks": (0, INF),
        ".net.transits": (0, INF),
        ".net.hist": (0, INF),
        ".sched.inst_of_rank": (-1, caps.max_instances - 1),
        ".sched.svc_replicas": (0, caps.max_replicas),
        ".svc_stats.finished": (-INF, INF),
        ".fault.host_up": (0, 1),
        ".fault.nic_ok": (0, 1),
        ".fault.host_slow": (0, 1),
        ".fault.zone_cut": (0, 1),
        ".fault.edge_succ": (0, INF),
        ".fault.inst_succ": (0, INF),
        ".alerts.astate": (0, 3),
        ".alerts.ev_service": (-1, S - 1),
        ".alerts.ev_rule": (0, 7),
        ".alerts.ev_state": (0, 3),
        "_E_sentinel": (0, E),    # referenced by tests; not a real leaf
    }


def seed_vals(sim, state, dyn):
    """IVal seeds for the flattened (state, dyn, app) argument list, plus
    the path list used for the inductive output check."""
    from repro.core.types import POOL_COLUMN_BOUNDS
    caps, app = sim.caps, sim.app
    rules = _state_bound_rules(caps, app)
    layout = state.cloudlets.layout

    def pool_cols(fields):
        cs = tuple(POOL_COLUMN_BOUNDS[n](caps, app) for n in fields)
        return ival(min(c[0] for c in cs), max(c[1] for c in cs),
                    cols=cs, col_axis=1)

    state_leaves = jtu.tree_flatten_with_path(state)[0]
    paths, vals = [], []
    for p, leaf in state_leaves:
        ks = jtu.keystr(p)
        if ks.startswith(".cloudlets"):
            fields = (layout.i_fields if "index 0" in ks else layout.f_fields)
            v = pool_cols(fields)
        elif ks in rules:
            v = ival(*rules[ks])
        elif ks.startswith((".counters.", ".fstats.", ".qos.", ".slo.")):
            # accumulators and tallies; never feed an index computation
            v = ival(-INF, INF)
        else:
            dt = getattr(leaf, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.floating):
                v = ival(-INF, INF)
            else:
                v = top_for(leaf)
        paths.append(ks)
        vals.append(v)

    # dyn params: user-facing rates/thresholds, documented nonnegative
    dyn_leaves = jtu.tree_flatten(dyn)[0]
    dyn_vals = [ival(0, INF) for _ in dyn_leaves]
    # app: concrete build-validated tables → exact seeds
    app_vals = [from_concrete(leaf) for leaf in jtu.tree_flatten(app)[0]]
    return paths, vals, dyn_vals, app_vals


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ComboReport:
    combo: str
    sites: List[Site]
    induction_fails: List[str]
    unknown_prims: Dict[str, int]

    @property
    def violations(self) -> List[Site]:
        return [s for s in self.sites if not s.ok]

    def summary(self) -> str:
        n_ok = sum(1 for s in self.sites if s.ok)
        return (f"{self.combo}: {len(self.sites)} sites, {n_ok} ok, "
                f"{len(self.violations)} violations, "
                f"{len(self.induction_fails)} induction failures")


def analyze_jaxpr(closed, invals) -> Tuple[List[Site], List[IVal], Interp]:
    """Library entry for tests: interpret one ClosedJaxpr with given seeds."""
    it = Interp()
    outs = it.run(closed, invals)
    return it.sites, outs, it


def verify_combo(network: str, faults: str, *, sim=None,
                 telemetry: str = "none") -> ComboReport:
    """Prove index safety of one combo's tick program."""
    from repro.core.types import DynParams
    from .layout_check import _tiny_sim

    sim = sim or _tiny_sim(network, faults, False, telemetry)
    state = sim.init_state()
    dyn = DynParams.from_params(sim.params)
    closed = jax.make_jaxpr(sim._tick)(state, dyn, sim.app)

    paths, svals, dvals, avals = seed_vals(sim, state, dyn)
    it = Interp()
    outs = it.run(closed, list(svals) + list(dvals) + list(avals))

    # inductive step: the tick's output state must stay inside the seeds
    out_shapes = jax.eval_shape(sim._tick, state, dyn, sim.app)
    out_paths = [jtu.keystr(p)
                 for p, _ in jtu.tree_flatten_with_path(out_shapes)[0]]
    seed_by_path = dict(zip(paths, svals))
    fails = []
    for ks, ov in zip(out_paths, outs):
        key = ks[3:] if ks.startswith("[0]") else None   # "[0].tick" → ".tick"
        if key is None or key not in seed_by_path:
            continue
        sv = seed_by_path[key]
        if sv.cols is not None and ov.cols is not None \
                and len(sv.cols) == len(ov.cols):
            for f, (slh, olh) in enumerate(zip(sv.cols, ov.cols)):
                if not (olh[0] >= slh[0] and olh[1] <= slh[1]):
                    fails.append(
                        f"{key}[col {f}]: out [{olh[0]:g},{olh[1]:g}] ⊄ "
                        f"seed [{slh[0]:g},{slh[1]:g}]")
        elif not _contained(ov, sv):
            fails.append(f"{key}: out [{ov.lo:g},{ov.hi:g}] ⊄ "
                         f"seed [{sv.lo:g},{sv.hi:g}]")
    return ComboReport(f"{network}+{faults}", it.sites, fails,
                       dict(it.unknown))

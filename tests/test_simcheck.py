"""simcheck self-tests (DESIGN.md §8).

Three layers:

* **golden topology** — the four mode combos' RNG stream-derivation
  trees pinned under digests, so a widened split or reordered fold_in
  fails here before it silently perturbs every seeded experiment
  (`jax.random.split` is not prefix-stable);
* **seeded violations** — each analyzer rule is fed a deliberately
  broken input and must fire: a checker that cannot catch its own
  seeded bug is decoration;
* **layout properties** — reading a column absent from a mode's layout
  raises (never silently aliases another column), for every combo.

The recompile sentinel's full warm/count pass runs in the CI simcheck
job (`python -m repro.analysis`), not here — this file only proves the
counter counts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import checkify

from repro.analysis import jaxpr_lint, layout_check, recompile, streams
from repro.analysis.simcheck import check_streams, run_simcheck
from repro.core.pool import SlotAssignment, scatter_pool
from repro.core.types import PHASE_COLUMNS, _layout_for

# Pinned stream-derivation topologies (see analysis/streams.py).  If an
# engine change legitimately rewires a stream tree, re-pin via
#   python -c "from repro.analysis.simcheck import check_streams; \
#              print(check_streams()['digests'])"
# and say so in the commit — this is the seeded-run compatibility break.
GOLDEN_STREAM_DIGESTS = {
    "uniform+none": "63d3efb9556990fb",
    "uniform+chaos": "ef15e81868ba91e7",
    "fabric+none": "3c57f57cd8b23c38",
    "fabric+chaos": "bceab1a96eb2745f",
    # telemetry is observation-only: NO tick RNG consumed, so the fifth
    # combo's topology is pinned EQUAL to fabric+chaos (PR 8)
    "fabric+chaos+telemetry": "bceab1a96eb2745f",
    # alerting is pure arithmetic over sealed SLI windows: same pin (PR 9)
    "fabric+chaos+alerting": "bceab1a96eb2745f",
}


# ---------------------------------------------------------------------------
# Golden topology + clean integration
# ---------------------------------------------------------------------------

def test_stream_topology_matches_golden():
    res = check_streams()
    assert res["problems"] == []
    assert res["digests"] == GOLDEN_STREAM_DIGESTS


def test_layout_and_streams_sections_clean():
    report = run_simcheck(only={"layout", "streams"})
    assert report.ok, report.problems


def test_lint_combo_clean_uniform_none():
    assert jaxpr_lint.lint_combo("uniform", "none") == []


# ---------------------------------------------------------------------------
# Seeded violations: jaxpr lint
# ---------------------------------------------------------------------------

def test_lint_catches_f64_in_hot_loop():
    def leaky(x):
        return (x.astype(jnp.float64) * 2.0).astype(jnp.float32)

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(leaky)(jnp.ones((4,), jnp.float32))
    probs = jaxpr_lint.lint_jaxpr(closed, in_loop=True)
    assert any(p.startswith("f64:") for p in probs)
    # ...and the rule is waivable by id
    assert jaxpr_lint.lint_jaxpr(closed, in_loop=True,
                                 waive={"f64"}) == []


def test_lint_catches_callback_in_hot_loop():
    def chatty(x):
        jax.debug.print("x={x}", x=x)
        return x + 1.0

    closed = jax.make_jaxpr(chatty)(jnp.float32(0.0))
    probs = jaxpr_lint.lint_jaxpr(closed, in_loop=True)
    assert any(p.startswith("callback:") for p in probs)


def test_lint_ignores_cold_code():
    # Same callback OUTSIDE any loop: in_loop=False keeps it legal.
    def chatty(x):
        jax.debug.print("x={x}", x=x)
        return x + 1.0

    closed = jax.make_jaxpr(chatty)(jnp.float32(0.0))
    assert jaxpr_lint.lint_jaxpr(closed, in_loop=False) == []


def test_donation_check_catches_undonated_carry():
    def bump(st):
        return jax.tree_util.tree_map(
            lambda x: x + jnp.ones((), x.dtype), st)

    state = {"a": jnp.zeros((4,), jnp.float32),
             "b": jnp.zeros((2,), jnp.int32)}
    undonated = jax.jit(bump).lower(state)
    probs = jaxpr_lint.check_donation(undonated)
    assert probs and probs[0].startswith("donation:")
    assert jaxpr_lint.check_donation(undonated, waive={"donation"}) == []

    donated = jax.jit(bump, donate_argnums=0).lower(state)
    assert jaxpr_lint.check_donation(donated) == []


# ---------------------------------------------------------------------------
# Seeded violations: layout-access checker
# ---------------------------------------------------------------------------

def test_layout_checker_catches_undeclared_access():
    # Strip 'wait_ticks' from Dispatch's declaration: the replay still
    # reads it (the real layout is untouched), so the access is now
    # undeclared and must fail.
    perturbed = dict(PHASE_COLUMNS)
    perturbed["Dispatch"] = tuple(
        c for c in PHASE_COLUMNS["Dispatch"] if c != "wait_ticks")
    probs = layout_check.check_layout_access(phase_columns=perturbed)
    assert any("undeclared" in p and "wait_ticks" in p
               and "'Dispatch'" in p for p in probs)


def test_layout_checker_catches_stale_declaration():
    perturbed = dict(PHASE_COLUMNS)
    perturbed["Execute"] = PHASE_COLUMNS["Execute"] + ("ghost_col",)
    probs = layout_check.check_layout_access(phase_columns=perturbed)
    assert any("ever touches" in p and "ghost_col" in p for p in probs)


def test_layout_checker_clean_on_real_registry():
    assert layout_check.check_layout_access() == []


# ---------------------------------------------------------------------------
# Layout property: absent-column reads raise under every mode combo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("network,faults,egress,telemetry",
                         layout_check.COMBOS)
def test_absent_column_read_raises(network, faults, egress, telemetry):
    full = _layout_for("fabric", "chaos", True)
    layout = _layout_for(network, faults, egress, telemetry)
    for col in full.i_fields:
        if col not in layout.i_fields:
            with pytest.raises(KeyError):
                layout.i(col)
    for col in full.f_fields:
        if col not in layout.f_fields:
            with pytest.raises(KeyError):
                layout.f(col)
    with pytest.raises(KeyError):
        layout.i("definitely_not_a_column")
    with pytest.raises(KeyError):
        layout.f("definitely_not_a_column")


# ---------------------------------------------------------------------------
# Seeded violations: RNG stream auditor
# ---------------------------------------------------------------------------

def test_streams_catch_key_reuse():
    key = jax.random.PRNGKey(0)
    with streams.recording() as rec:
        rec.register(key, "root")
        streams.split(key, names=("a", "b"))
        streams.split(key, names=("a", "b"))   # identical derivation
    probs = streams.audit_events(rec)
    assert any("key reuse" in p for p in probs)


def test_streams_catch_path_collision():
    key = jax.random.PRNGKey(0)
    with streams.recording() as rec:
        rec.register(key, "root")
        streams.fold_in(key, 1, name="x")
        streams.fold_in(key, 2, name="x")      # distinct stream, same name
    probs = streams.audit_events(rec)
    assert any("path collision" in p for p in probs)


def test_streams_catch_unnamed_derivation():
    key = jax.random.PRNGKey(0)
    with streams.recording() as rec:
        rec.register(key, "root")
        orphan = jax.random.fold_in(key, 7)    # raw call — unwrapped site
        streams.split(orphan, names=("a", "b"))
    probs = streams.audit_events(rec)
    assert any("unnamed stream" in p for p in probs)


def test_streams_validate_names():
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError):
        streams.split(key, 3, names=("a", "b"))
    with pytest.raises(ValueError):
        streams.split(key, names=("a", "a"))


def test_streams_are_transparent_outside_recording():
    key = jax.random.PRNGKey(0)
    named = streams.split(key, 3, names=("a", "b", "c"))
    raw = jax.random.split(key, 3)
    assert (jax.numpy.asarray(named) == jax.numpy.asarray(raw)).all()
    assert (streams.fold_in(key, 5, name="x")
            == jax.random.fold_in(key, 5)).all()


# ---------------------------------------------------------------------------
# Seeded violation: recompile counter
# ---------------------------------------------------------------------------

def test_compile_counter_counts_cache_misses():
    with recompile.count_backend_compiles() as hits:
        for i in range(3):
            # a fresh function object per iteration defeats the jit
            # cache — exactly the closure bug the sentinel hunts
            jax.jit(lambda x, _i=i: x + _i)(jnp.float32(0.0))
    assert hits[0] >= 3


def test_compile_counter_silent_on_cache_hits():
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.float32(1.0))                        # warm
    with recompile.count_backend_compiles() as hits:
        for s in range(5):
            f(jnp.float32(s))                  # value changes, shape fixed
    assert hits[0] == 0


# ---------------------------------------------------------------------------
# REPRO_CHECKED=1: checkify lowering of declared-disjoint sites
# ---------------------------------------------------------------------------

def test_checked_mode_is_value_neutral(monkeypatch):
    # The checkify asserts must not perturb the simulation: same seeds,
    # same results, checked or not.
    monkeypatch.delenv("REPRO_CHECKED", raising=False)
    res0 = layout_check._tiny_sim("fabric", "chaos", False, False).run()
    monkeypatch.setenv("REPRO_CHECKED", "1")
    res1 = layout_check._tiny_sim("fabric", "chaos", False, False).run()
    np.testing.assert_array_equal(
        np.asarray(res0.state.requests.response),
        np.asarray(res1.state.requests.response))
    assert int(res0.state.counters.finished) == \
        int(res1.state.counters.finished)


def _forged_scatter(dst):
    """scatter_pool call with a hand-forged (invalid) slot assignment."""
    sim = layout_check._tiny_sim("uniform", "none", False, False)
    cl = sim.init_state().cloudlets
    cols = {n: 0 for n in cl.layout.columns}
    i32 = jnp.int32
    asg = SlotAssignment(dst=jnp.asarray(dst, i32),
                         src=jnp.arange(len(dst), dtype=i32),
                         live=jnp.ones((len(dst),), bool),
                         n_assigned=jnp.asarray(len(dst), i32),
                         n_dropped=jnp.asarray(0, i32))
    return cl, lambda c: scatter_pool(c, asg, **cols)


def test_checked_mode_catches_duplicate_slots(monkeypatch):
    monkeypatch.setenv("REPRO_CHECKED", "1")
    cl, fn = _forged_scatter([3, 3])    # two live lanes, one slot
    err, _out = checkify.checkify(fn, errors=checkify.user_checks)(cl)
    with pytest.raises(Exception, match="duplicate destination slot"):
        err.throw()


def test_checked_mode_catches_oob_live_destination(monkeypatch):
    monkeypatch.setenv("REPRO_CHECKED", "1")
    cl, fn = _forged_scatter([-5])      # live lane below the pool
    err, _out = checkify.checkify(fn, errors=checkify.user_checks)(cl)
    with pytest.raises(Exception, match="destination out of range"):
        err.throw()


def test_unchecked_mode_traces_no_asserts(monkeypatch):
    # Without REPRO_CHECKED the same forged call is assert-free (the
    # production program carries zero checkify overhead).
    monkeypatch.delenv("REPRO_CHECKED", raising=False)
    cl, fn = _forged_scatter([3, 3])
    err, _out = checkify.checkify(fn, errors=checkify.user_checks)(cl)
    assert err.get() is None

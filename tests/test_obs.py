"""Observability tests (DESIGN.md §9).

Four contracts:

* **observation-only** — turning ``telemetry="stream"`` ON leaves every
  simulated leaf of the state and trace bit-identical to the same
  scenario run with telemetry off, in every mode combo (the OFF program
  itself is pinned by
  ``test_layouts.test_mode_matrix_bit_identical_golden``);
* **exact overflow accounting** — the span ring never silently caps:
  ``span_n + span_drops`` equals the number of spans the run *wanted*
  to record, to the unit;
* **trace reconstruction** — a sampled request's span tree reproduces
  the engine's recorded response with tolerance ZERO, both by timestamp
  identity and by the tropical (max-plus) closure over the span DAG
  (``core/critical_path.py``'s Alg 2 at span granularity);
* **streamed == aggregate** — rows streamed through the io_callback
  exporter during ``run_batch`` reconcile exactly with the end-of-run
  ``QoSReport`` per sweep point.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import SimParams, Simulation
from repro.core.engine import batch_item
from repro.core.qos import summarize
from repro.obs import export
from repro.obs import spans as spans_mod
from repro.obs import telemetry as telmod

from test_layouts import MODES, assert_same_simulation, matrix_sim


def _d_max(sim: Simulation) -> int:
    return int(sim.app.succ.shape[1])


# ---------------------------------------------------------------------------
# Observation-only: telemetry ON changes no simulated bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("network,faults", MODES)
def test_telemetry_on_bit_identical_golden(network, faults):
    """The chunked scan-of-scan + ring writes + span sampling must not
    perturb a single simulated bit: every leaf of the state and trace
    equals the plain run's with telemetry streaming."""
    sim = matrix_sim(network, faults, telemetry="stream",
                     tel_window_ticks=16, tel_windows=8,
                     tel_span_k=4, tel_span_cap=256)
    with export.collecting() as col:
        res = sim.run()
    assert_same_simulation(res, matrix_sim(network, faults).run())
    # ...and the observation stream itself is well-formed: one row per
    # closed window (300 ticks / 16 = 18), schema-valid.
    rows = col.rows
    assert len(rows) == 300 // 16
    export.validate_rows(rows)


# ---------------------------------------------------------------------------
# Span ring: exact overflow accounting
# ---------------------------------------------------------------------------

def test_span_ring_overflow_counts_drops_exactly():
    """tel_span_k=1 samples EVERY request, so the run wants one span per
    finished cloudlet; a tiny ring must fill to capacity and count every
    rejected span — spans kept + spans dropped == cloudlets finished."""
    cap = 8
    sim = matrix_sim("uniform", "none", telemetry="stream",
                     tel_window_ticks=16, tel_windows=8,
                     tel_span_k=1, tel_span_cap=cap)
    res = sim.run()
    tel = res.state.telemetry
    span_n = int(np.asarray(tel.span_n)[0])
    drops = int(np.asarray(tel.span_drops)[0])
    finished = int(res.state.counters.finished)
    assert finished > cap                    # scenario actually overflows
    assert span_n == cap                     # full, never overwritten
    assert drops == finished - cap           # every drop counted, exactly
    # the report surfaces the same numbers
    rep = summarize(sim, res)
    assert rep.tel_spans == cap
    assert rep.tel_span_drops == drops


def test_span_tick_cap_exact_accounting():
    """``tel_span_tick_cap`` bounds the per-tick staging build (the ring
    capacity otherwise re-inflates it); a generous budget is bitwise
    identical to uncapped, and a starved one still conserves
    kept + dropped == finished — drops are counted, never silent."""
    kw = dict(telemetry="stream", tel_window_ticks=16, tel_windows=8,
              tel_span_k=1, tel_span_cap=2048)
    base = matrix_sim("uniform", "none", **kw).run()
    roomy = matrix_sim("uniform", "none", tel_span_tick_cap=512,
                       **kw).run()       # 512 = pool size: can't bind
    for f in ("span_i", "span_f", "span_n", "span_drops"):
        np.testing.assert_array_equal(
            np.asarray(getattr(base.state.telemetry, f)),
            np.asarray(getattr(roomy.state.telemetry, f)))
    tight = matrix_sim("uniform", "none", tel_span_tick_cap=1,
                       **kw).run()       # ≤ 1 span staged per tick
    tel = tight.state.telemetry
    span_n = int(np.asarray(tel.span_n)[0])
    drops = int(np.asarray(tel.span_drops)[0])
    finished = int(tight.state.counters.finished)
    assert span_n + drops == finished    # conservation survives the cap
    assert span_n < int(np.asarray(base.state.telemetry.span_n)[0])
    assert span_n <= 300                 # matrix_sim runs 300 ticks


# ---------------------------------------------------------------------------
# Trace reconstruction: span tree == engine response, tolerance 0
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("network,faults", [("uniform", "none"),
                                            ("fabric", "chaos")])
def test_sampled_trace_reproduces_response_exactly(network, faults):
    """Every completed, retry-free, non-failed sampled request's span
    tree must reproduce the engine's response bitwise — both the
    timestamp identity and the tropical closure (TraceCheck.exact)."""
    sim = matrix_sim(network, faults, telemetry="stream",
                     tel_window_ticks=16, tel_windows=8,
                     tel_span_k=2, tel_span_cap=1024)
    res = sim.run()
    checks = spans_mod.verify_traces(res.state, sim.graph, _d_max(sim))
    eligible = [c for c in checks if not c.failed and c.retry_free]
    assert len(eligible) >= 5, "scenario produced too few sampled traces"
    for c in eligible:
        assert c.tree == c.response, \
            f"req {c.req}: tree {c.tree!r} != response {c.response!r}"
        assert c.tropical == c.response, \
            f"req {c.req}: tropical {c.tropical!r} != {c.response!r}"
        assert c.exact
        # graph-level Alg 2 is f32-approximate — consistency only
        if c.graph is not None:
            np.testing.assert_allclose(float(c.graph), float(c.response),
                                       rtol=1e-5, atol=1e-5)


def test_trace_tree_shape_matches_graph():
    """A sampled diamond-request's tree has the entry as root and every
    span parented by a span whose finish equals its arrival bitwise."""
    sim = matrix_sim("fabric", "chaos", telemetry="stream",
                     tel_window_ticks=16, tel_windows=8,
                     tel_span_k=1, tel_span_cap=2048)
    res = sim.run()
    checks = spans_mod.verify_traces(res.state, sim.graph, _d_max(sim))
    full = [c for c in checks
            if not c.failed and c.retry_free and c.n_spans >= 4]
    assert full, "no fully-fanned diamond trace sampled"
    req = full[0].req
    roots = spans_mod.trace_tree(spans_mod.spans_of(res.state, req),
                                 sim.graph.n_services, _d_max(sim))
    assert len(roots) == 1                   # single client→entry root
    for s in spans_mod._all_spans(roots):
        if s.parent is not None:
            assert np.float32(s.parent.finish) == np.float32(s.arrival)
    assert spans_mod.format_trace(roots)     # renders without error


# ---------------------------------------------------------------------------
# run_batch: streamed rows reconcile with QoSReport per sweep point
# ---------------------------------------------------------------------------

def test_run_batch_streamed_rows_match_reports():
    """Each sweep point's streamed windows must cover the whole run
    (n_ticks a multiple of the window) and their sums/finals equal the
    point's QoSReport aggregates computed from the final state."""
    base = matrix_sim("fabric", "chaos", telemetry="stream", n_ticks=128,
                      tel_window_ticks=8, tel_windows=4,
                      tel_span_k=2, tel_span_cap=512)
    points = [dataclasses.replace(base.params, spawn_rate=r)
              for r in (3.0, 5.0, 8.0)]
    with export.collecting() as col:
        res = base.run_batch(points)
    rows = col.rows
    export.validate_rows(rows)
    n_windows = 128 // 8
    for b, p in enumerate(points):
        mine = [r for r in rows if int(r["tag"]) == b]
        assert len(mine) == n_windows, \
            f"point {b}: {len(mine)} rows streamed, want {n_windows}"
        item = batch_item(res, b)
        rep = summarize(base, item, params=p)
        # windowed counters sum to the run totals
        assert int(sum(r["completed"] for r in mine)) \
            == rep.completed_requests
        assert int(sum(r["generated"] for r in mine)) \
            == rep.generated_requests
        # cumulative gauges: the last window reports the final state
        last = max(mine, key=lambda r: r["window"])
        assert int(last["failed_attempts"]) \
            == int(item.state.fstats.failed_attempts)
        assert int(last["retries"]) == rep.retries
        assert int(last["spans"]) == rep.tel_spans
        assert int(last["span_drops"]) == rep.tel_span_drops
        assert rep.tel_windows == n_windows
        # per-tick trace cross-check: window sums == trace sums
        tr = np.asarray(item.trace.completed)
        assert int(sum(r["completed"] for r in mine)) == int(tr.sum())


def test_solo_run_flushes_live_and_drains_tail():
    """A solo run whose tick count is NOT flush-aligned still delivers
    every closed window: chunk flushes live + end-of-run drain."""
    sim = matrix_sim("uniform", "none", telemetry="stream", n_ticks=100,
                     tel_window_ticks=8, tel_windows=4,
                     tel_span_k=4, tel_span_cap=128)
    with export.collecting() as col:
        sim.run()
    rows = col.rows
    # 100 ticks / 8 = 12 closed windows; chunk = 8*2 = 16 ticks → 6
    # live flushes deliver 12 rows... all of them here; the drain covers
    # whatever the final partial chunk sealed.
    assert len(rows) == 100 // 8
    export.validate_rows(rows)
    assert [int(r["window"]) for r in
            sorted(rows, key=lambda r: r["window"])] == list(range(12))


def test_telemetry_off_streams_nothing():
    sim = matrix_sim("uniform", "none", n_ticks=64)
    with export.collecting() as col:
        res = sim.run()
    assert col.rows == []
    assert res.state.telemetry.ring.size == 0
    rep = summarize(sim, res)
    assert (rep.tel_windows, rep.tel_spans, rep.tel_span_drops) == (0, 0, 0)


def test_flush_ticks_and_window_validation():
    from repro.core.types import validate_telemetry
    assert telmod.flush_ticks(SimParams(tel_window_ticks=16,
                                        tel_windows=8)) == 64
    with pytest.raises(ValueError, match="even"):
        validate_telemetry(SimParams(telemetry="stream", tel_windows=3))
    with pytest.raises(ValueError, match="'none' or 'stream'"):
        validate_telemetry(SimParams(telemetry="sometimes"))

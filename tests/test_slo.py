"""SLO alerting tests (DESIGN.md §10).

Five contracts:

* **observation-only** — turning ``alerting="burn"`` ON (objectives
  disabled, telemetry streaming) leaves every simulated leaf of the
  state and trace bit-identical to the plain run, in every mode combo;
* **burn-rate math** — device-side f32 rule evaluation matches a
  host-side float64 oracle over crafted SLI windows with decisive
  margins (no f32-rounding knife edges);
* **state machine** — pending → firing → resolved round-trips on a
  crafted condition sequence, with ``for_ticks`` hysteresis and exact
  one-shot fire/resolve counting;
* **streamed == aggregate** — ALERTS transition rows drained during
  ``run_batch`` reconcile exactly with each point's QoSReport counters;
* **feedback gating** — ``hs_mode="slo_burn"`` scales out only on
  firing alerts (never when objectives are disabled).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (InstanceTemplate, SimCaps, SimParams, Simulation,
                        diamond, policies)
from repro.core.engine import batch_item
from repro.core.qos import summarize
from repro.core.types import (ALERT_FIRING, ALERT_INACTIVE, ALERT_PENDING,
                              ALERT_RESOLVED, DynParams, validate_alerting)
from repro.obs import export
from repro.obs import slo as slomod

from test_layouts import MODES, assert_same_simulation, matrix_sim

# observation-only telemetry + alerting riders for the golden scenario:
# objectives stay DISABLED (slo_budget=0.0 default) so the rule
# conditions are constant-false and nothing feeds back.
ALERT_KW = dict(telemetry="stream", tel_window_ticks=16, tel_windows=8,
                tel_span_k=4, tel_span_cap=256, alerting="burn")

# an always-burning variant: slo_ms=1.0 makes every completion an SLO
# miss (frac = 1.0), budget 0.05 → burn 20 ≥ both thresholds.
HOT_KW = dict(ALERT_KW, slo_budget=0.05, slo_ms=1.0,
              slo_short_wins=2, slo_long_wins=4, slo_for_ticks=2)


# ---------------------------------------------------------------------------
# Observation-only: alerting ON (objectives off) changes no simulated bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("network,faults", MODES)
def test_alerting_on_bit_identical_golden(network, faults):
    """The Alerting stage rides the carry in every mode combo without
    perturbing a single simulated bit while no objective is enabled —
    the burn conditions are constant-false, the feedback multipliers
    exact identities."""
    sim = matrix_sim(network, faults, **ALERT_KW)
    res = sim.run()
    assert_same_simulation(res, matrix_sim(network, faults).run())
    # ...and the alert plane stayed silent: no objective, no transitions
    rep = summarize(sim, res)
    assert (rep.alert_fires, rep.alert_resolves,
            rep.alert_event_drops) == (0, 0, 0)
    assert rep.alert_firing_time_s == 0.0


def test_alerting_off_is_zero_width():
    sim = matrix_sim("uniform", "none", n_ticks=64)
    res = sim.run()
    al = res.state.alerts
    assert al.astate.size == 0 and al.sli_win.size == 0
    assert al.ev_time.size == 0
    rep = summarize(sim, res)
    assert (rep.alert_fires, rep.alert_resolves,
            rep.alert_event_drops) == (0, 0, 0)


# ---------------------------------------------------------------------------
# Burn-rate math vs a host-side float64 oracle
# ---------------------------------------------------------------------------

def _oracle_rules(sli_win, w_closed, budget, params):
    """Mirror of evaluate_rules in plain float64 numpy: iterate window
    ids m in [w_closed - n, w_closed), read ring slot m % L."""
    sli = np.asarray(sli_win, np.float64)
    L, S, _ = sli.shape

    def frac(n):
        out = np.zeros(S)
        for s in range(S):
            good = bad = 0.0
            for m in range(max(0, w_closed - n), w_closed):
                good += sli[m % L, s, 0]
                bad += sli[m % L, s, 1]
            out[s] = bad / max(good + bad, 1.0)
        return out

    b = np.asarray(budget, np.float64)
    active = b > 0
    safe = np.maximum(b, 1e-9)
    burn1 = frac(1) / safe
    burn_s = frac(params.slo_short_wins) / safe
    burn_l = frac(params.slo_long_wins) / safe
    fast = active & (burn_s >= params.slo_fast_burn) \
        & (burn1 >= params.slo_fast_burn)
    slow = active & (burn_l >= params.slo_slow_burn) \
        & (burn_s >= params.slo_slow_burn)
    return np.stack([fast, slow], axis=1)


def test_burn_rules_match_float64_oracle():
    """Device f32 rule conditions == host f64 oracle over crafted SLI
    rings: full burn, partial burn landing between the two thresholds,
    recovered services, empty windows, disabled objectives, and a
    partially-filled ring (w_closed < L)."""
    params = SimParams(telemetry="stream", alerting="burn",
                       slo_budget=0.05, slo_short_wins=2, slo_long_wins=4,
                       slo_fast_burn=14.4, slo_slow_burn=6.0)
    dyn = DynParams.from_params(params)
    L, S = 6, 5
    rng = np.random.RandomState(11)
    for w_closed in (0, 1, 3, 6, 11):
        sli = np.zeros((L, S, 2), np.float32)
        for m in range(max(0, w_closed - L), w_closed):
            # decisive margins only: frac per (window, service) is one of
            # {0, 0.5, 1} — burn {0, 10, 20} vs thresholds 14.4 / 6.0
            kind = rng.randint(0, 3, size=S)
            n = rng.randint(1, 40, size=S).astype(np.float32)
            sli[m % L, :, 0] = np.where(kind == 0, n,
                                        np.where(kind == 1, n, 0.0))
            sli[m % L, :, 1] = np.where(kind == 0, 0.0,
                                        np.where(kind == 1, n, n))
        budget = np.array([0.05, 0.05, 0.0, -1.0, 0.05], np.float32)
        got = np.asarray(slomod.evaluate_rules(
            jnp.asarray(sli), jnp.int32(w_closed), jnp.asarray(budget),
            params, dyn))
        want = _oracle_rules(sli, w_closed, budget, params)
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"w_closed={w_closed}")
        # disabled objectives can never fire
        assert not got[2].any() and not got[3].any()


def test_lookback_frac_ring_wraparound():
    """Slot contents older than the lookback are excluded even after the
    ring wraps: window ids resolve to the LARGEST m < w_closed at each
    slot, so a 1-window lookback reads exactly the newest window."""
    L, S = 3, 1
    sli = np.zeros((L, S, 2), np.float32)
    # windows 2,3,4 live in slots 2,0,1; window 4 (slot 1) is all-bad,
    # the older two all-good
    sli[2, 0] = (10.0, 0.0)     # window 2
    sli[0, 0] = (10.0, 0.0)     # window 3
    sli[1, 0] = (0.0, 10.0)     # window 4
    f1 = float(slomod._lookback_frac(jnp.asarray(sli), jnp.int32(5), 1)[0])
    f3 = float(slomod._lookback_frac(jnp.asarray(sli), jnp.int32(5), 3)[0])
    assert f1 == 1.0
    assert abs(f3 - 10.0 / 30.0) < 1e-6


# ---------------------------------------------------------------------------
# State machine: pending → firing → resolved with for_ticks hysteresis
# ---------------------------------------------------------------------------

def _drive(conds, for_ticks):
    st = jnp.full((1,), ALERT_INACTIVE, jnp.int32)
    pend = jnp.zeros((1,), jnp.int32)
    out = []
    for c in conds:
        st, pend = slomod.step_machine(st, pend,
                                       jnp.asarray([c]), for_ticks)
        out.append(int(st[0]))
    return out


def test_state_machine_round_trip():
    # for_ticks=3: two pending ticks, fire on the third held tick, stay
    # firing under the condition, resolve one tick after it clears, then
    # back to inactive.
    assert _drive([1, 1, 1, 1, 0, 0], for_ticks=3) == [
        ALERT_PENDING, ALERT_PENDING, ALERT_FIRING, ALERT_FIRING,
        ALERT_RESOLVED, ALERT_INACTIVE]


def test_state_machine_hysteresis_resets_on_gap():
    # a gap during pending resets the held counter — the alert never
    # fires on intermittent flapping shorter than for_ticks.
    assert _drive([1, 1, 0, 1, 1, 0, 1], for_ticks=3) == [
        ALERT_PENDING, ALERT_PENDING, ALERT_INACTIVE,
        ALERT_PENDING, ALERT_PENDING, ALERT_INACTIVE, ALERT_PENDING]


def test_state_machine_for_ticks_one_fires_immediately():
    assert _drive([1, 0, 1], for_ticks=1) == [
        ALERT_FIRING, ALERT_RESOLVED, ALERT_FIRING]


def test_state_machine_refire_after_resolve():
    # resolved is a one-tick state; a re-burn restarts the full
    # hysteresis from pending.
    assert _drive([1, 1, 0, 0, 1, 1], for_ticks=2) == [
        ALERT_PENDING, ALERT_FIRING, ALERT_RESOLVED, ALERT_INACTIVE,
        ALERT_PENDING, ALERT_FIRING]


# ---------------------------------------------------------------------------
# End-to-end: a hot run fires, resolves at run end, and reconciles
# ---------------------------------------------------------------------------

def test_hot_run_fires_and_reports():
    """slo_ms=1.0 turns every completion into an SLO miss: the fast rule
    must fire on the entry service and the report's firing time must
    equal firing_ticks * dt to the float."""
    sim = matrix_sim("uniform", "none", **HOT_KW)
    res = sim.run()
    rep = summarize(sim, res)
    assert rep.alert_fires > 0
    assert rep.alert_event_drops == 0
    al = res.state.alerts
    assert rep.alert_firing_time_s == pytest.approx(
        float(np.asarray(al.firing_ticks).sum()) * sim.params.dt)
    # drained events replay the exact transition counts
    rows = slomod.drain_events(al)
    export.validate_alert_rows(rows)
    assert sum(r["state"] == "firing" for r in rows) == rep.alert_fires
    assert sum(r["state"] == "resolved" for r in rows) == rep.alert_resolves
    # exposition formats render without error
    for r in rows[:4]:
        assert "ALERTS{" in export.prometheus_alert_line(r)
        assert export.otel_alert_event(r)


def test_event_ring_overflow_counts_drops_exactly():
    sim = matrix_sim("uniform", "none", **dict(HOT_KW, slo_event_cap=2))
    res = sim.run()
    al = res.state.alerts
    n = int(np.asarray(al.ev_n)[0])
    drops = int(np.asarray(al.ev_drops)[0])
    transitions = int(np.asarray(al.fires).sum()
                      + np.asarray(al.resolves).sum())
    assert n == 2                        # full, never overwritten
    assert drops > 0
    # every transition either landed in the ring or was counted dropped
    # (pending/inactive transitions also occupy the ring, so >=)
    assert n + drops >= transitions
    assert summarize(sim, res).alert_event_drops == drops


def test_run_batch_alert_rows_match_reports():
    """Per sweep point, streamed ALERTS transition rows reconcile
    EXACTLY with the point's QoSReport fire/resolve counters."""
    base = matrix_sim("uniform", "none", **HOT_KW)
    points = [dataclasses.replace(base.params, spawn_rate=r)
              for r in (3.0, 5.0, 8.0)]
    with export.alert_collecting() as col:
        res = base.run_batch(points)
    rows = col.rows
    export.validate_alert_rows(rows)
    assert rows, "hot scenario streamed no alert transitions"
    for b, p in enumerate(points):
        mine = [r for r in rows if int(r["tag"]) == b]
        rep = summarize(base, batch_item(res, b), params=p)
        assert rep.alert_event_drops == 0
        assert sum(r["state"] == "firing" for r in mine) == rep.alert_fires
        assert sum(r["state"] == "resolved" for r in mine) \
            == rep.alert_resolves
        assert rep.alert_fires > 0


# ---------------------------------------------------------------------------
# Feedback gating: hs_mode="slo_burn" scales out only on firing alerts
# ---------------------------------------------------------------------------

def _burn_sim(**over):
    # the golden scenario starts AT the replica cap, so feedback tests
    # use their own sim: 1 replica per service with headroom to 4.
    caps = SimCaps(n_clients=16, max_requests=512, max_cloudlets=512,
                   max_instances=16, n_vms=4, d_max=2, max_replicas=4)
    kw = dict(dt=0.05, n_ticks=300, n_clients=12, spawn_rate=5.0,
              wait_lo=0.5, wait_hi=1.5, seed=3, net_latency_s=0.05,
              scaling_policy=policies.SCALE_HORIZONTAL, scale_interval=20,
              hs_mode="slo_burn", slo_stabilize_s=2.0, **HOT_KW)
    kw.update(over)
    return Simulation(diamond(mi=400.0), caps=caps, params=SimParams(**kw),
                      default_template=InstanceTemplate(
                          mips=8000.0, limit_mips=16000.0, replicas=1),
                      vm_mips=np.full(4, 64000.0, np.float32))


def test_slo_burn_autoscaler_scales_out_on_firing():
    res = _burn_sim().run()
    assert int(res.state.counters.scale_out) > 0


def test_slo_burn_autoscaler_idle_without_objectives():
    # objectives disabled → alerts never fire → the burn gate never
    # scales out (the util path would have, under the same load)
    res = _burn_sim(slo_budget=0.0, slo_ms=1000.0).run()
    assert int(res.state.counters.scale_out) == 0


# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------

def test_validate_alerting_rejects_bad_configs():
    with pytest.raises(ValueError, match="telemetry"):
        validate_alerting(SimParams(alerting="burn"))
    with pytest.raises(ValueError, match="alerting"):
        validate_alerting(SimParams(alerting="sometimes"))
    with pytest.raises(ValueError, match="hs_mode"):
        validate_alerting(SimParams(hs_mode="vibes"))
    with pytest.raises(ValueError, match="slo_long_wins"):
        validate_alerting(SimParams(telemetry="stream", alerting="burn",
                                    slo_short_wins=4, slo_long_wins=2))
    with pytest.raises(ValueError, match="slo_burn"):
        validate_alerting(SimParams(hs_mode="slo_burn"))

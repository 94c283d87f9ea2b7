"""What decides ``correct`` must fail where the answers are wrong: the
control (each configuration's plain reference computed in bfloat16, the
precision below the configuration's float32), and the harness driven over
a broken timed path, once for each fault a cell can have."""
from __future__ import annotations

import json

import ml_dtypes
import numpy as np
import pytest

import bench_testkit as tk
from cnsbench import spec

CELLS = {"case1b.seeds": ("case1b", "seeds"),
         "sockshop-hs.u300": ("sockshop-hs", "u300")}


def _reference(config: str):
    return spec.load_module(tk.BENCH_DIR / "configs"
                            / f"{config}.reference.py")


def _traffic(name: str) -> dict:
    return json.loads((tk.BENCH_DIR / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("seed", [11, 2 ** 31 - 5, 2718281828 & 0x7FFFFFFF])
def test_control_in_bfloat16_fails(cell, seed):
    config, traffic = CELLS[cell]
    ref = _reference(config)
    cfg = tk.tiny_config(config)
    want = ref.run(cfg, _traffic(traffic), seed)
    got = ref.run(cfg, _traffic(traffic), seed, ml_dtypes.bfloat16)
    gaps = ref.gaps(want, got)
    assert [k for k, v in gaps.items() if v > ref.LIMITS[k]], gaps


def _unchanged(sim, res):
    """A job whose steps return the state they were given."""
    state = sim.init_state(0)
    return res.state._replace(requests=state.requests,
                              counters=state.counters), res.trace._replace(
        generated=res.trace.generated * 0)


def _half_left_out(sim, res):
    """Half of the requests' answers left out."""
    r = res.state.requests.response.at[::2].set(-1.0)
    return res.state._replace(
        requests=res.state.requests._replace(response=r)), res.trace


def _one_answer_altered(sim, res):
    """One request's response altered where it is produced: 2 ms late."""
    r = res.state.requests.response
    done = np.flatnonzero(np.asarray(r) >= 0)
    r = r.at[int(done[len(done) // 3])].add(0.002)
    return res.state._replace(
        requests=res.state.requests._replace(response=r)), res.trace


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _one_answer_altered],
                         ids=["state_unchanged", "half_left_out",
                              "answer_altered"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, cell,
                                          fault):
    from repro.core import Simulation

    real_run = Simulation.run

    def broken_run(self, seed=None):
        res = real_run(self, seed)
        state, trc = fault(self, res)
        return res.__class__(state=state, trace=trc,
                             wall_time_s=res.wall_time_s,
                             compile_time_s=res.compile_time_s)

    monkeypatch.setattr(Simulation, "run", broken_run)
    root, bdir = tk.tiny_bench(tmp_path, cell)
    rc, res, err = tk.run_bench(
        ["--workload", cell, "--seed", "4000000007", "--seconds", "0.5",
         "--trace", "0"], root, bdir)
    assert rc == 0 and res["correct"] is False, err[-2000:]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct_on_the_cpu(tmp_path, cell):
    """The reference reproduces the program's jobs: every count agrees
    and responses agree to a few float32 rounding steps."""
    root, bdir = tk.tiny_bench(tmp_path, cell)
    rc, res, err = tk.run_bench(
        ["--workload", cell, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], root, bdir)
    assert rc == 0 and res["correct"] is True, err[-2000:]
    assert res["failed"] == 0 and res["attempted"] >= 1
    checks = dict(res["checks"])
    assert checks.pop("response_gap_ms")["value"] < 0.01
    assert all(c["value"] == 0 for c in checks.values()), checks

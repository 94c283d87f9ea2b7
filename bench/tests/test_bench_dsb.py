"""The DeathStarBench social-network cell on the CPU, cut to a few
clients: a sound run is correct, its control (the reference in bfloat16)
fails, and a program that routes calls by service instead of by the
request's API (every callee of a service called on every request, as
before per-API call trees) is not correct."""
from __future__ import annotations

import json

import ml_dtypes
import pytest

import bench_testkit as tk
from cnsbench import spec

CELL = "dsb-social.mixed"
CONFIG = "dsb-social"
# cut to a CPU test: ~400 clients over 2 simulated s
TINY_CAPS = dict(n_clients=512, max_requests=8000, max_cloudlets=1024)
TINY_TICKS = 200
TINY_PARAMS = dict(n_clients=400, spawn_rate=1000.0)


def tiny_cell(tmp_path) -> tuple:
    """(root, bench_dir) of a copy of the benchmark holding the cell, its
    configuration and traffic cut to a CPU test."""
    root, bdir = tk.tiny_bench(tmp_path, CELL)
    cfg = json.loads((tk.BENCH_DIR / "configs" / f"{CONFIG}.json")
                     .read_text())
    cfg["caps"].update(TINY_CAPS)
    cfg["params"]["n_ticks"] = TINY_TICKS
    (bdir / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    path = bdir / "traffic" / "dsb-mixed.json"
    traffic = json.loads(path.read_text())
    traffic["params"].update(TINY_PARAMS)
    path.write_text(json.dumps(traffic))
    return root, bdir


def _run(tmp_path, seed: str) -> tuple:
    root, bdir = tiny_cell(tmp_path)
    return tk.run_bench(["--workload", CELL, "--seed", seed, "--seconds",
                         "0.5", "--trace", "0"], root, bdir)


def test_sound_run_is_correct_on_the_cpu(tmp_path):
    rc, res, err = _run(tmp_path, "3000000019")
    assert rc == 0 and res["correct"] is True, err[-2000:]
    checks = dict(res["checks"])
    assert checks.pop("response_gap_ms")["value"] < 0.01
    assert all(c["value"] == 0 for c in checks.values()), checks


def test_routing_by_service_is_not_correct(tmp_path, monkeypatch):
    from repro.core import Simulation

    real_init = Simulation.__init__

    def by_service(self, graph, *a, **kw):
        graph.route_p = None          # every callee, whatever the API
        real_init(self, graph, *a, **kw)

    monkeypatch.setattr(Simulation, "__init__", by_service)
    rc, res, err = _run(tmp_path, "4000000007")
    assert rc == 0 and res["correct"] is False, err[-2000:]
    assert res["checks"]["calls_per_request_differing"]["value"] > 0


@pytest.mark.parametrize("seed", [11, 2 ** 31 - 5])
def test_control_in_bfloat16_fails(seed):
    ref = spec.load_module(tk.BENCH_DIR / "configs"
                           / f"{CONFIG}.reference.py")
    cfg = json.loads((tk.BENCH_DIR / "configs" / f"{CONFIG}.json")
                     .read_text())
    cfg["caps"].update(TINY_CAPS)
    cfg["params"]["n_ticks"] = TINY_TICKS
    traffic = dict(name="tiny", kind="solo", params=TINY_PARAMS)
    gaps = ref.gaps(ref.run(cfg, traffic, seed),
                    ref.run(cfg, traffic, seed, ml_dtypes.bfloat16))
    assert [k for k, v in gaps.items() if v > ref.LIMITS[k]], gaps

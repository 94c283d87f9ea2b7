#!/usr/bin/env python3
"""Find a cell's knee: the highest closed-loop load its simulated
deployment sustains.

    python3 bench/tools/load_sweep.py WORKLOAD SEED N_CLIENTS [N_CLIENTS ...]

Builds the cell as ``bench/run.py`` does, then runs one ``run_batch``
job over the given client counts, each ramped in over the first fifth of
the job, and prints one JSON line per point: requests admitted and
completed per simulated second after the ramp, and the cloudlets
dropped.  A point is sustained where completions stay within 5% of
admissions and no cloudlet is dropped.  It then times three solo jobs of
the cell as it stands (``ms_per_tick``).  The engine runs on the default
device: the chip, where there is one.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]

from cnsbench import build, spec  # noqa: E402

SUSTAINED = 0.05      # completions within this share of admissions
RAMP_SHARE = 0.2      # clients ramp in over this share of the job


def sweep(sim, clients, seed: int) -> list:
    from repro.core import batch_item

    p = sim.params
    ramp_s = RAMP_SHARE * p.n_ticks * p.dt
    points = [dataclasses.replace(p, n_clients=int(n),
                                  spawn_rate=n / ramp_s) for n in clients]
    res = sim.run_batch(points, seed=seed)
    t0 = int(np.ceil(RAMP_SHARE * p.n_ticks))
    window_s = (p.n_ticks - t0) * p.dt
    out = []
    for b, n in enumerate(clients):
        r = batch_item(res, b)
        adm = float(np.asarray(r.trace.generated)[t0:].sum()) / window_s
        done = float(np.asarray(r.trace.completed)[t0:].sum()) / window_s
        dropped = int(r.state.counters.dropped_cloudlets)
        resp = np.asarray(r.state.requests.response)
        out.append(dict(
            n_clients=int(n), admitted_per_s=adm, completed_per_s=done,
            dropped_cloudlets=dropped,
            admitted=int(r.state.requests.count),
            response_ms_mean=float(resp[resp >= 0].mean() * 1e3),
            peak_in_flight=int(np.asarray(r.trace.n_waiting
                                          + r.trace.n_exec
                                          + r.trace.n_transit).max()),
            sustained=bool(dropped == 0
                           and done >= (1 - SUSTAINED) * adm)))
    return out


def main(workload: str, seed: int, clients) -> int:
    cell = spec.load_cell(workload, BENCH_DIR.parent)
    sim = build.build(cell.config, cell.traffic)
    for row in sweep(sim, clients, seed):
        print(json.dumps(dict(workload=workload, **row)), flush=True)
    walls = []
    for k in range(3):
        t0 = time.perf_counter()
        res = sim.run(seed=seed + k)
        walls.append(time.perf_counter() - t0)
    print(json.dumps(dict(
        workload=workload, job_walls_s=walls,
        engine_ms_per_tick=res.wall_time_s * 1e3 / sim.params.n_ticks,
        device=str(res.state.tick.devices()))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]),
                  [int(n) for n in sys.argv[3:]]))

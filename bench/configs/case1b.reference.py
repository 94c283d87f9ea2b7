"""Plain reference for Table 2 case 1 (``case1b``), in NumPy.

It runs the same simulation as the engine, one tick at a time, from the
same seed, with none of the engine's code: closed-loop clients (paper
Alg 1), one root cloudlet per admitted request, round-robin dispatch over
the service's identical replicas, equal time slices with sub-tick finish
times, and a request's response at its last cloudlet's finish.  The
seeded stream is drawn with ``jax.random`` on the CPU as the engine draws
it: each tick splits the carried key five ways (carry, generation,
spawn, balancer, successors); generation splits its key into (API,
wait) draws, and spawn draws one standard normal per admission rank.

``dtype`` is the precision of every real number of the simulation:
``float32`` as the configuration states, and ``bfloat16`` for the control
(the reference in the nearest lower precision, which must fail).

``compare`` gives the numbers that decide ``correct`` for a sample of
the timed jobs, each with its limit in ``LIMITS``.
"""
from __future__ import annotations

import functools
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from cnsbench.build import capacity_sizes  # noqa: E402
from cnsbench.jobs import point_traffics  # noqa: E402

CL_FREE, CL_WAITING, CL_EXEC = 0, 1, 2

# Limits of the compared numbers (PERF.md gives the readings they were
# set from).  Admissions and which requests finished are exact.
LIMITS = {
    "admitted_ticks_differing": 0,
    "finished_requests_differing": 0,
    "response_gap_ms": 1.0,
}


@functools.lru_cache(maxsize=4)
def _drawer(n_ticks: int, n_clients: int, n_ranks: int):
    import jax

    def body(key, _):
        carry, k_gen, k_spawn, _, _ = jax.random.split(key, 5)
        _, k_wait = jax.random.split(k_gen, 2)
        return carry, (jax.random.uniform(k_wait, (n_clients,)),
                       jax.random.normal(k_spawn, (n_ranks,)))

    return jax.jit(lambda key: jax.lax.scan(body, key, None,
                                            length=n_ticks)[1])


def seeded_draws(seed: int, n_ticks: int, n_clients: int, n_ranks: int):
    """Per tick: the clients' uniform wait draws [T, Nc] and the spawn
    wave's standard normals [T, K], drawn on the CPU."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        u, z = _drawer(n_ticks, n_clients, n_ranks)(jax.random.PRNGKey(seed))
        return np.asarray(u), np.asarray(z)


def params_of(config: dict, traffic: dict) -> dict:
    """The run's parameters: the configuration's sizes, with the
    traffic's load settings in place."""
    t2 = config["table2"]
    z = capacity_sizes(t2)
    p = dict(dt=t2["dt"], n_ticks=z["n_ticks"], n_clients=z["n_clients"],
             spawn_rate=z["n_clients"] / 5.0, wait_lo=t2["wait_lo"],
             wait_hi=t2["wait_hi"], num_limit=t2["n_requests"])
    p.update(traffic.get("params", {}))
    return dict(p, caps_clients=z["n_clients"], R=z["max_requests"],
                C=z["max_cloudlets"], K=min(z["k_fire"], z["n_clients"]),
                replicas=t2["replicas"], mips=z["mips"],
                len_mean=t2["mi"], len_std=0.1 * t2["mi"])


def simulate(p: dict, seed: int, dtype=np.float32) -> dict:
    """Responses of every request slot (-1 while open) and the requests
    admitted in each tick."""
    ft = np.dtype(dtype).type
    T, Nc, R, C, K = p["n_ticks"], p["caps_clients"], p["R"], p["C"], p["K"]
    reps = p["replicas"]
    n_clients = min(int(p["n_clients"]), Nc)
    num_limit = int(p["num_limit"])
    u_all, z_all = seeded_draws(seed, T, Nc, min(C, K))

    dt = ft(p["dt"])
    lo, hi = ft(p["wait_lo"]), ft(p["wait_hi"])
    rate0 = ft(p["mips"])
    mean, std = ft(p["len_mean"]), ft(np.float32(p["len_std"]))
    spawn_rate = ft(p["spawn_rate"])
    eps = ft(1e-6)

    wait = np.zeros(Nc, np.int64)
    idx = np.arange(Nc)
    count = 0
    arrival = np.full(R, -1.0, dtype)
    finish = np.zeros(R, dtype)
    response = np.full(R, -1.0, dtype)
    outstanding = np.zeros(R, np.int64)
    spawned = np.zeros(R, np.int64)
    status = np.zeros(C, np.int64)
    req = np.full(C, -1, np.int64)
    inst = np.full(C, -1, np.int64)
    rem = np.zeros(C, dtype)
    arr_cl = np.zeros(C, dtype)
    n_exec = np.zeros(reps, np.int64)
    rr = 0
    admitted = np.zeros(T, np.int64)
    time = ft(0.0)
    slots = np.arange(C)

    for t in range(T):
        # --- Generation: Alg 1 fire decisions, admission budget, spawn
        n_active = min(n_clients, int(np.floor(spawn_rate * time)) + 1)
        fired = (idx < n_active) & (wait <= 0) & (count < num_limit)
        wait_s = lo + (hi - lo) * u_all[t].astype(dtype)
        proposal = np.maximum(np.round(wait_s / dt), 1).astype(np.int64)
        rank = np.cumsum(fired) - 1
        in_budget = fired & (rank < K) & (count + rank < num_limit)
        has_slot = in_budget & (count + rank < R)
        n_accept = int(has_slot.sum())
        wait = np.where(in_budget, proposal,
                        np.where(fired, 0, np.maximum(wait - 1, 0)))
        new_req = count + np.arange(n_accept)
        arrival[new_req] = time
        free = np.flatnonzero(status == CL_FREE)
        n_new = min(len(free), n_accept, len(z_all[t]))
        dst = free[:n_new]
        length = np.maximum(mean + std * z_all[t][:n_new].astype(dtype),
                            ft(1.0))
        status[dst] = CL_WAITING
        req[dst] = new_req[:n_new]
        rem[dst] = length
        arr_cl[dst] = time
        outstanding[new_req[:n_new]] += 1
        spawned[new_req[:n_new]] += 1
        count += n_accept
        admitted[t] = n_accept

        # --- Dispatch: round-robin over the replicas, in slot order
        waiting = (status == CL_WAITING) & (time + eps >= arr_cl)
        target = (rr + slots) % reps
        status[waiting] = CL_EXEC
        inst[waiting] = target[waiting]
        np.add.at(n_exec, target[waiting], 1)
        rr = (rr + int(waiting.sum())) % reps

        # --- Execute: equal time slices, sub-tick finish times
        ex = status == CL_EXEC
        rate = np.zeros(C, dtype)
        rate[ex] = rate0 / n_exec[inst[ex]].astype(dtype)
        prog = rate * dt
        fin = ex & (rem <= prog) & (rate > 0)
        safe = np.where(fin, rate, ft(1.0))
        tfin = np.clip(time + rem / safe, time, time + dt)
        rem = np.where(ex, np.maximum(rem - prog, ft(0.0)), rem)
        r_fin = req[fin]
        np.maximum.at(finish, r_fin, tfin[fin])
        np.subtract.at(outstanding, r_fin, 1)
        np.subtract.at(n_exec, inst[fin], 1)
        status[fin] = CL_FREE
        inst[fin] = -1

        # --- Response: requests whose last cloudlet finished
        cand = np.unique(r_fin)
        done = cand[(outstanding[cand] == 0) & (spawned[cand] > 0)
                    & (response[cand] < 0) & (arrival[cand] >= 0)]
        response[done] = finish[done] - arrival[done]
        time = ft(time + dt)

    return dict(response=response.astype(np.float64), count=count,
                generated=admitted)


def run(config: dict, traffic: dict, seed: int, dtype=np.float32) -> dict:
    """One job of the cell, as the reference computes it."""
    return simulate(params_of(config, traffic), seed, dtype)


def gaps(ref: dict, got: dict) -> dict:
    """The compared numbers of one job (the program's outputs ``got``)."""
    r = np.asarray(ref["response"], np.float64)
    g = np.asarray(got["response"], np.float64)
    both = (r >= 0) & (g >= 0)
    gap = float(np.abs(r[both] - g[both]).max() * 1e3) if both.any() \
        else 0.0
    return {
        "admitted_ticks_differing": int(
            (np.asarray(ref["generated"]) != np.asarray(got["generated"]))
            .sum()),
        "finished_requests_differing": int(((r >= 0) != (g >= 0)).sum()),
        "response_gap_ms": gap,
    }


def compare(config: dict, traffic: dict, jobs: list,
            dtype=np.float32) -> dict:
    """The worst of each compared number over ``jobs`` (the timed jobs'
    ``JobOut``s), each simulated point against its own run."""
    worst: dict = {}
    for job in jobs:
        for out, point in zip(job.outputs, point_traffics(traffic)):
            ref = run(config, point, job.seed, dtype)
            for k, v in gaps(ref, out).items():
                worst[k] = max(worst.get(k, v), v)
    return worst

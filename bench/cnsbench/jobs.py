"""Simulation jobs and the closed loop that runs them.

A job is what a user of the simulator submits: one ``Simulation.run``
(traffic kind ``solo``) or one ``Simulation.run_batch`` over the
traffic's sweep points (kind ``batch``), with a seed derived from the
run's ``--seed`` and the job's index.  Each job's device work is a
fixed-length scan, so the window's cost is whole jobs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

# index of the job that warms the program up during set-up
WARMUP_JOB = 1 << 20
CL_FREE = 0                 # a free cloudlet slot (core.types.CL_FREE)
NO_LIMIT = 2 ** 31 - 1      # SimParams.num_limit when none is set


def job_seed(run_seed: int, k: int) -> int:
    """Seed of job ``k`` of a run: the same run seed gives the same
    jobs, and seeds fit the 31 bits ``Simulation.run`` takes."""
    words = np.random.SeedSequence([int(run_seed), int(k)]).generate_state(1)
    return int(words[0]) & 0x7FFFFFFF


def point_traffics(traffic: dict) -> list:
    """One traffic per simulated point of a job: the traffic itself for a
    ``solo`` job; for a ``batch`` job, each sweep point's parameters over
    the traffic's own."""
    if traffic.get("kind", "solo") == "solo":
        return [traffic]
    base = traffic.get("params", {})
    return [dict(traffic, params=dict(base, **p)) for p in traffic["points"]]


@dataclasses.dataclass
class JobOut:
    """What a job hands back: host copies of the outputs the reference
    compares, and the result of the invariant checks."""
    seed: int
    sim_s: float
    wall_s: float              # host clock around the whole job
    call_s: float              # host clock around the run / run_batch call
    engine_wall_s: float       # SimResult.wall_time_s (device scan)
    failures: list
    outputs: list              # one dict per simulated point


def _invariants(state, num_limit: int) -> list:
    """Exact integer conservation of cloudlets, and admission."""
    out = []
    spawned = int(state.counters.spawned)
    finished = int(state.counters.finished)
    in_flight = int((np.asarray(state.cloudlets.status) != CL_FREE).sum())
    dropped = int(state.fstats.failed_attempts)
    if spawned != finished + in_flight + dropped:
        out.append(f"spawned {spawned} != finished {finished} + in flight "
                   f"{in_flight} + dropped {dropped}")
    admitted = int(state.requests.count)
    if num_limit != NO_LIMIT:
        if admitted != num_limit:
            out.append(f"admitted {admitted} requests, num_limit "
                       f"{num_limit}")
    else:
        written = int((np.asarray(state.requests.api) >= 0).sum())
        if admitted != written:
            out.append(f"admitted {admitted} requests, {written} recorded")
    return out


# Outputs a reference may ask for on top of responses, request count and
# admissions per tick.
EXTRA_OUTPUTS = {
    "spawned": lambda st: np.asarray(st.requests.spawned),
    "replicas": lambda st: np.asarray(st.sched.svc_replicas),
    "failed_requests": lambda st: int(st.fstats.failed_requests),
}


def _readback(state, trace, extra=()) -> dict:
    """The per-request and per-tick outputs a reference compares."""
    out = dict(response=np.asarray(state.requests.response),
               count=int(state.requests.count),
               generated=np.asarray(trace.generated))
    out.update({k: EXTRA_OUTPUTS[k](state) for k in extra})
    return out


class Jobs:
    """Runs the jobs of one cell on one built ``Simulation``."""

    def __init__(self, sim, traffic: dict, run_seed: int, extra=()):
        self.sim = sim
        self.extra = tuple(extra)
        self.run_seed = run_seed
        kind = traffic.get("kind", "solo")
        if kind not in ("solo", "batch"):
            raise ValueError(f"unknown traffic kind {kind!r}")
        self.points = None
        if kind == "batch":
            self.points = [dataclasses.replace(sim.params, **t["params"])
                           for t in point_traffics(traffic)]
        n = 1 if self.points is None else len(self.points)
        self.sim_s = n * sim.params.n_ticks * sim.params.dt
        self.ticks = sim.params.n_ticks

    def run(self, k: int, hooks=None) -> JobOut:
        """Job ``k``.  ``hooks`` maps a stage (``run``, ``readback``,
        ``check``) to a context-manager factory that wraps it (the traced
        run's host spans)."""
        from repro.core import batch_item

        hooks = hooks or {}

        def span(stage):
            return hooks.get(stage, contextlib.nullcontext)()

        seed = job_seed(self.run_seed, k)
        t0 = time.perf_counter()
        with span("run"):
            if self.points is None:
                res = self.sim.run(seed=seed)
            else:
                res = self.sim.run_batch(self.points, seed=seed)
        call_s = time.perf_counter() - t0
        items = ([res] if self.points is None else
                 [batch_item(res, b) for b in range(len(self.points))])
        with span("readback"):
            outputs = [_readback(r.state, r.trace, self.extra)
                       for r in items]
        with span("check"):
            failures = []
            for b, r in enumerate(items):
                failures += [f"job {k} point {b}: {f}" for f in
                             _invariants(r.state, self.sim.params.num_limit)]
        wall = time.perf_counter() - t0
        return JobOut(seed=seed, sim_s=self.sim_s, wall_s=wall,
                      call_s=call_s, engine_wall_s=res.wall_time_s,
                      failures=failures, outputs=outputs)


@dataclasses.dataclass
class Window:
    jobs: list
    t_start: float
    t_end: float

    @property
    def wall_s(self) -> float:
        return self.t_end - self.t_start

    @property
    def sim_s(self) -> float:
        return sum(j.sim_s for j in self.jobs)


def closed_loop(jobs: Jobs, seconds: float) -> Window:
    """Back-to-back jobs, one at a time.  The first always starts; a
    later one starts only while its expected end (the mean wall of the
    window's jobs so far) falls inside ``seconds``."""
    done: list = []
    t_start = time.perf_counter()
    t_end = t_start
    while True:
        if done:
            expected = sum(j.wall_s for j in done) / len(done)
            if (t_end - t_start) + expected > seconds:
                break
        done.append(jobs.run(len(done)))
        t_end = time.perf_counter()
    return Window(jobs=done, t_start=t_start, t_end=t_end)

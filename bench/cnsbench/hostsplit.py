"""The device's idle time inside one job, split by what the host did.

The engine names each host stage of a ``Simulation.run`` call with a
span on the profiler's clock (``sim/run``, then ``sim/init_state``,
``sim/unalias``, ``sim/dyn_params``, ``sim/lookup``, ``sim/dispatch``,
``sim/wait``; ``repro/obs/hostspans.py``).  Against the device planes
of the same trace (``trace.read_xplane``) they give:

* ``host_setup_s``: idle device time inside ``sim/run`` before the run
  program's first execution starts (the host builds the job's state,
  op by op);
* ``in_program_s``: idle device time inside the run program's
  executions (``XLA Modules`` intervals): gaps between the scan's ops;
* ``setup_programs``: executions of other device programs that start
  inside ``sim/run`` (the eager ops of the state's construction);
* ``idle_gaps``: the longest idle gaps, each named by the innermost
  host span over it (of the ``bench/`` and ``sim/`` spans, the one
  overlapping the gap most, the shortest on a tie), with its start.

Idle time is measured as in ``trace.reduce_trace``: the window is the
``bench/job`` span, busy time the union of the leaf ops clipped to it;
numbers are means over the devices.
"""
from __future__ import annotations

import dataclasses

from cnsbench import trace

SIM_PREFIX = "sim/"
RUN_SPANS = ("sim/run", "sim/run_batch")


def read_sim_spans(path: str) -> list:
    """The engine's host spans (``sim/...``, full names) of a trace
    (``.xplane.pb``, or gzipped ``.xplane.pb.gz``)."""
    import gzip

    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    return [trace.Span(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(SIM_PREFIX)]


@dataclasses.dataclass
class HostSplit:
    host_setup_s: float
    in_program_s: float
    setup_programs: float
    idle_s: float          # all idle device time in the window
    idle_in_s: dict        # bench span name -> idle device seconds in it
    idle_gaps: list        # [(innermost host span, seconds, start s)]


def _idle(merged: list, a: float, b: float) -> float:
    """Length of [a, b) not covered by the merged busy intervals."""
    if b <= a:
        return 0.0
    busy = sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged)
    return (b - a) - busy


def split(devices: dict, spans: list, sim_spans: list, program: str,
          n_top: int = 10):
    """The split of the traced job's idle time (``None`` when the trace
    holds no device, no engine span or no run of ``program`` inside
    it).
    ``spans``: the harness's ``bench/`` spans (short names, as
    ``trace.read_xplane`` gives them); ``sim_spans``: ``read_sim_spans``."""
    window = [s for s in spans if s.name == "job"]
    if len(window) != 1:
        raise ValueError(f"expected one bench/job span, found {len(window)}")
    w0, w1 = window[0].start_ns, window[0].end_ns
    runs = [s for s in sim_spans if s.name in RUN_SPANS
            and w0 <= s.start_ns and s.end_ns <= w1]
    if len(runs) != 1 or not devices:
        return None
    r0, r1 = runs[0].start_ns, runs[0].end_ns
    n_dev = max(len(devices), 1)
    host_setup = in_program = n_setup = idle = 0.0
    idle_in: dict = {}
    gaps = []
    for plane in devices.values():
        execs = sorted((m.start_ns, m.start_ns + m.dur_ns)
                       for m in plane.modules
                       if m.name == program and r0 <= m.start_ns < r1)
        if not execs:
            return None
        merged = trace._union(
            (max(op.start_ns, w0), min(op.start_ns + op.dur_ns, w1))
            for op in trace._leaf_ops(plane.ops)
            if min(op.start_ns + op.dur_ns, w1) > max(op.start_ns, w0))
        idle += _idle(merged, w0, w1)
        host_setup += _idle(merged, r0, execs[0][0])
        in_program += sum(_idle(merged, s, min(e, w1)) for s, e in execs)
        n_setup += sum(1 for m in plane.modules
                       if m.name != program and r0 <= m.start_ns < r1)
        for s in spans:
            if s.name != "job":
                idle_in[s.name] = idle_in.get(s.name, 0.0) + _idle(
                    merged, max(s.start_ns, w0), min(s.end_ns, w1))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                 if g1 > g0]
    gaps.sort(key=lambda g: g[0] - g[1])
    inner = [s for s in spans if s.name != "job"] + list(sim_spans)
    return HostSplit(
        host_setup_s=host_setup / n_dev * 1e-9,
        in_program_s=in_program / n_dev * 1e-9,
        setup_programs=n_setup / n_dev,
        idle_s=idle / n_dev * 1e-9,
        idle_in_s={k: v / n_dev * 1e-9 for k, v in idle_in.items()},
        idle_gaps=[(innermost(inner, g0, g1), (g1 - g0) * 1e-9,
                    (g0 - w0) * 1e-9) for g0, g1 in gaps[:n_top]])


def innermost(spans: list, g0: float, g1: float) -> str:
    """The host span that overlaps [g0, g1) the most, the shortest of
    those that tie (``host`` if none)."""
    best, best_key = "host", (0.0, 0.0)
    for s in spans:
        ov = min(s.end_ns, g1) - max(s.start_ns, g0)
        if ov > 0 and (ov, -s.dur_ns) > best_key:
            best, best_key = s.name, (ov, -s.dur_ns)
    return best

#!/usr/bin/env python3
"""Split a traced job's device idle time by the engine's host stages.

    CNSBENCH_KEEP_TRACE=DIR python3 bench/run.py ... --trace 1
    python3 bench/tools/split_trace.py DIR

Reads the trace and program text that ``bench/run.py`` keeps in DIR
(``job.xplane.pb``, ``module.hlo.txt``, or their ``.gz``), and prints
one JSON line: the device's idle time inside ``sim/run`` before the run
program (``host_setup_ms``), inside the run program
(``in_program_ms``), in each ``bench/`` span and in all, the other
device programs run inside ``sim/run``, the host milliseconds of each
``sim/`` span, and the longest idle gaps (name of the innermost host
span, length and start in ms; ``cnsbench/hostsplit.py``).
"""
from __future__ import annotations

import gzip
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

from cnsbench import hostsplit, trace  # noqa: E402


def _file(d: pathlib.Path, name: str) -> pathlib.Path:
    return d / name if (d / name).exists() else d / (name + ".gz")


def summary(d: pathlib.Path) -> dict:
    """The split of the trace kept in ``d``, as one JSON-ready dict."""
    xplane = str(_file(d, "job.xplane.pb"))
    hlo = _file(d, "module.hlo.txt")
    opener = gzip.open if hlo.suffix == ".gz" else open
    with opener(hlo, "rt") as f:
        program = trace.module_name(f.read())
    devices, spans = trace.read_xplane(xplane)
    sims = hostsplit.read_sim_spans(xplane)
    sp = hostsplit.split(devices, spans, sims, program)
    if sp is None:
        raise SystemExit(f"no engine run span and device run of {program} "
                         f"in {xplane}")
    host_ms: dict = {}
    for s in sims:
        host_ms[s.name] = host_ms.get(s.name, 0.0) + s.dur_ns * 1e-6
    return dict(
        host_setup_ms=sp.host_setup_s * 1e3,
        in_program_ms=sp.in_program_s * 1e3,
        idle_in_ms={k: v * 1e3 for k, v in sp.idle_in_s.items()},
        setup_programs=sp.setup_programs,
        host_ms=host_ms,
        idle_ms=sp.idle_s * 1e3,
        idle_gaps=[[n, s * 1e3, t * 1e3] for n, s, t in sp.idle_gaps])


if __name__ == "__main__":
    print(json.dumps(summary(pathlib.Path(sys.argv[1]))))

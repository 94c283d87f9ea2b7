"""From a profiler trace of one job to per-layer numbers.

The traced run records one whole job under ``jax.profiler`` with host
spans (``TraceAnnotation``) named ``bench/<stage>`` around its stages.
The reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:

* device operations: the events of the device planes' ``XLA Ops`` line,
  each named by its HLO instruction's text (``%fusion.12 = ...``); the
  ``XLA Modules`` line says which program each ran in;
* phases: the compiled module's text (``compiled.as_text()``) gives each
  instruction of the run program an ``op_name`` whose path holds the
  tick phase's ``jax.named_scope`` (a fusion without one takes its fused
  computation's root's); an instruction whose path names no phase is
  ``unattributed``, and an op of any other program (the job's host-side
  set-up runs small ones) is ``other programs``;
* the traced window is the host span ``bench/job``; busy time is the
  union of the device operations' intervals inside it; a control-flow
  op's event, which spans the ops it runs, is not counted as an op.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

# Tick phases named by the engine's jax.named_scope wrappers.
PHASES = ("Generation", "Disruption", "Transit", "Dispatch", "Execute",
          "Telemetry", "Alerting", "Derive", "Response", "Scaling", "Trace")
SPAN_PREFIX = "bench/"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNATTRIBUTED = "unattributed"
OTHER_PROGRAMS = "other programs"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def phase_of_path(op_name: str) -> str:
    """The tick phase in an ``op_name`` path (the innermost one)."""
    found = UNATTRIBUTED
    for part in op_name.split("/"):
        if part in PHASES:
            found = part
    return found


def instruction_name(event_name: str) -> str:
    """``fusion.12`` of a device event named ``%fusion.12 = f32[..] ...``
    (or named ``fusion.12`` alone)."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.lstrip("%").split(" ")[0]


def module_name(hlo_text: str) -> str:
    m = _MODULE.match(hlo_text)
    return m.group(1) if m else ""


def phase_map(hlo_text: str) -> dict:
    """HLO instruction name -> tick phase, for every instruction of the
    module text; a fusion whose own line carries no ``op_name`` takes the
    phase of its fused computation's root."""
    out, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c and "=" not in line.split("{")[0]:
            comp = c.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        out[name] = phase_of_path(op.group(1)) if op else UNATTRIBUTED
        call = _CALLS.search(line)
        if not op and call:
            calls[name] = call.group(1)
        if line.lstrip().startswith("ROOT") and comp is not None:
            roots[comp] = out[name]
    for name, comp in calls.items():
        out[name] = roots.get(comp, UNATTRIBUTED)
    return out


@dataclasses.dataclass
class DeviceOp:
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


@dataclasses.dataclass
class DevicePlane:
    ops: list          # [DeviceOp], by instruction name
    modules: list      # [DeviceOp], program executions by module name


def read_xplane(path: str):
    """(device planes by name, bench host spans) of one trace
    (``.xplane.pb``, or gzipped ``.xplane.pb.gz``)."""
    import gzip

    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [DeviceOp(instruction_name(ev.name),
                                     float(ev.start_ns),
                                     float(ev.duration_ns))
                            for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules += [DeviceOp(ev.name.split("(")[0],
                                         float(ev.start_ns),
                                         float(ev.duration_ns))
                                for ev in line.events]
            if ops:
                devices[plane.name] = DevicePlane(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name[len(SPAN_PREFIX):],
                                          float(ev.start_ns),
                                          float(ev.duration_ns)))
    return devices, spans


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over the devices used
    n_ops: int                    # device op events in the window, all chips
    phase_s: dict                 # phase -> device op seconds (mean/chip)
    top_ops: list                 # [(Phase/hlo name, seconds)]
    idle_gaps: list               # [(host span covering it, seconds)]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _program_intervals(modules: list, program: str) -> list:
    return sorted((m.start_ns, m.start_ns + m.dur_ns) for m in modules
                  if m.name == program)


def _inside(intervals: list, t: float) -> bool:
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= t < intervals[i][1]


def reduce_trace(devices: dict, spans: list, phases: dict, program: str,
                 n_top: int = 10) -> Reduction:
    """Numbers of the traced window (the ``job`` span).  ``phases`` maps
    the instructions of the module ``program`` (the run program)."""
    window = [s for s in spans if s.name == "job"]
    if len(window) != 1:
        raise ValueError(f"expected one bench/job span, found {len(window)}")
    w0, w1 = window[0].start_ns, window[0].end_ns
    inner = [s for s in spans if s.name != "job"]
    n_dev = max(len(devices), 1)
    busy = 0.0
    n_ops = 0
    phase_ns: dict = {}
    op_ns: dict = {}
    gaps = []
    for plane in devices.values():
        runs = _program_intervals(plane.modules, program)
        ivs = []
        for op in _leaf_ops(plane.ops):
            s, e = max(op.start_ns, w0), min(op.start_ns + op.dur_ns, w1)
            if e <= s:
                continue
            n_ops += 1
            ivs.append((s, e))
            phase = (phases.get(op.name, UNATTRIBUTED)
                     if _inside(runs, op.start_ns) else OTHER_PROGRAMS)
            phase_ns[phase] = phase_ns.get(phase, 0.0) + (e - s)
            key = f"{phase}/{op.name}"
            op_ns[key] = op_ns.get(key, 0.0) + (e - s)
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g0, g1))
    gaps.sort(key=lambda g: g[0] - g[1])
    named_gaps = [(_covering(inner, g0, g1), (g1 - g0) * 1e-9)
                  for g0, g1 in gaps[:n_top]]
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:n_top]
    return Reduction(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / n_dev * 1e-9, n_ops=n_ops,
        phase_s={p: v / n_dev * 1e-9 for p, v in phase_ns.items()},
        top_ops=[(k, v / n_dev * 1e-9) for k, v in top],
        idle_gaps=named_gaps)


def _leaf_ops(ops: list) -> list:
    """The ops that run: a control-flow op (a ``while`` loop, a
    conditional) appears as an event spanning the ops it runs, which are
    counted instead.  Ops of one device run one at a time, so an event
    that a later one starts inside is such a container."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start_ns >= op.start_ns + op.dur_ns]


def _covering(spans: list, g0: float, g1: float) -> str:
    """The host span that overlaps the gap the most (``host`` if none)."""
    best, best_ov = "host", 0.0
    for s in spans:
        ov = min(s.end_ns, g1) - max(s.start_ns, g0)
        if ov > best_ov:
            best, best_ov = s.name, ov
    return best

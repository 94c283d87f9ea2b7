"""The benchmark's readers of the engine's own spans and counters, and
the split of a traced job's idle time by host stage."""
from __future__ import annotations

import gzip
import json
import pathlib
import types

import pytest

import bench_testkit as tk
from cnsbench import hostsplit, spec, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
ENGINE_METRICS = ("host_ms.init_state", "host_ms.unalias",
                  "host_ms.dyn_params", "host_ms.dispatch",
                  "setup_compile_s", "setup_backend_compiles")


def _synthetic():
    """A 20 ms job: the engine's spans inside ``bench/run``, two set-up
    programs before the run program, one readback program after it."""
    ms = 1e6
    op, span = trace.DeviceOp, trace.Span
    ops = [op("convert.1", 2 * ms, 1 * ms),
           op("broadcast.1", 4 * ms, 0.5 * ms),
           op("while.1", 8.5 * ms, 5 * ms),     # spans the loop's ops
           op("fusion.1", 8.5 * ms, 2 * ms),
           op("fusion.2", 11 * ms, 2.5 * ms),
           op("copy.1", 17 * ms, 0.5 * ms)]
    modules = [op("jit_convert_element_type", 2 * ms, 1 * ms),
               op("jit_broadcast_in_dim", 4 * ms, 0.5 * ms),
               op("jit_run_fn", 8.5 * ms, 5 * ms),
               op("jit_copy", 17 * ms, 0.5 * ms)]
    spans = [span("job", 0, 20 * ms), span("run", 0, 16 * ms),
             span("readback", 16 * ms, 2 * ms),
             span("check", 18 * ms, 2 * ms)]
    sims = [span("sim/run", 1 * ms, 14 * ms),
            span("sim/init_state", 1 * ms, 4 * ms),
            span("sim/unalias", 5 * ms, 1 * ms),
            span("sim/dyn_params", 6 * ms, 1 * ms),
            span("sim/lookup", 7 * ms, 1 * ms),
            span("sim/dispatch", 8 * ms, 1 * ms),
            span("sim/wait", 9 * ms, 5 * ms)]
    return {"/device:TPU:0": trace.DevicePlane(ops, modules)}, spans, sims


def test_idle_split_of_a_synthetic_trace():
    devices, spans, sims = _synthetic()
    sp = hostsplit.split(devices, spans, sims, "jit_run_fn")
    assert sp.host_setup_s == pytest.approx(0.006)   # [1, 8.5) less 1.5
    assert sp.in_program_s == pytest.approx(0.0005)  # [10.5, 11)
    assert sp.setup_programs == 2
    assert sp.idle_in_s == pytest.approx(
        {"run": 0.010, "readback": 0.0015, "check": 0.002})
    # a gap under several spans takes the one overlapping it most, the
    # shortest of a tie: [4.5, 8.5) lies wholly in run and sim/run only
    assert [(n, round(s * 1e3, 9), round(t * 1e3, 9))
            for n, s, t in sp.idle_gaps] == [
        ("sim/run", 4, 4.5), ("run", 3.5, 13.5), ("check", 2.5, 17.5),
        ("run", 2, 0), ("sim/init_state", 1, 3), ("sim/wait", 0.5, 10.5)]
    # the split adds up to the reduction's idle time: the rest of the
    # idle is before sim/run, after the run program, and after sim/run
    red = trace.reduce_trace(devices, spans, {}, "jit_run_fn")
    assert sp.idle_s == pytest.approx(red.window_s - red.busy_s)
    outside = 0.001 + 0.0015 + 0.001
    assert (sp.host_setup_s + sp.in_program_s + sp.idle_in_s["readback"]
            + sp.idle_in_s["check"] + outside) == pytest.approx(sp.idle_s)


def test_without_engine_spans_there_is_no_split_and_names_stay():
    """The trace recorded before the engine had spans: no split, and the
    reduction names its gaps as it always did."""
    path = str(DATA / "job.xplane.pb.gz")
    devices, spans = trace.read_xplane(path)
    assert hostsplit.read_sim_spans(path) == []
    assert hostsplit.split(devices, spans, [], "jit_run_fn") is None
    red = trace.reduce_trace(devices, spans, {}, "jit_run_fn")
    assert {n for n, _ in red.idle_gaps} <= {"run", "readback", "check"}


def test_split_of_the_recorded_chip_trace():
    """A tiny case1b job traced on a v5e with the engine's spans
    (``bench/tools/record_trace.py``, ``data/spans/``): the split's
    values, and how they fit the reduction and the run's result."""
    d = DATA / "spans"
    want = json.loads((d / "result.json").read_text())
    path = str(d / "job.xplane.pb.gz")
    devices, spans = trace.read_xplane(path)
    sims = hostsplit.read_sim_spans(path)
    with gzip.open(d / "module.hlo.txt.gz", "rt") as f:
        program = trace.module_name(f.read())
    sp = hostsplit.split(devices, spans, sims, program)
    assert want["device"]["platform"] == "tpu" and len(devices) == 1
    assert sp.host_setup_s == pytest.approx(0.120571331, rel=1e-9)
    assert sp.in_program_s == pytest.approx(0.002433283, rel=1e-9)
    assert sp.setup_programs == 250
    # the split's idle time is the reduction's, which the run printed
    assert sp.idle_s == pytest.approx(
        want["device"]["window_s"] - want["device"]["busy_s"], rel=1e-9)
    assert sp.host_setup_s + sp.in_program_s <= sp.idle_in_s["run"]
    named = (sp.host_setup_s + sp.in_program_s + sp.idle_in_s["readback"]
             + sp.idle_in_s["check"])
    assert 0.97 * sp.idle_s <= named <= sp.idle_s
    # the engine's stages cover its call to within 1 ms, in order
    (run,) = [s for s in sims if s.name == "sim/run"]
    stages = sorted((s for s in sims if s.name != "sim/run"),
                    key=lambda s: s.start_ns)
    assert [s.name for s in stages] == [
        "sim/init_state", "sim/unalias", "sim/dyn_params", "sim/lookup",
        "sim/dispatch", "sim/wait"]
    assert run.dur_ns - sum(s.dur_ns for s in stages) < 1e6
    dur_ms = {s.name: s.dur_ns * 1e-6 for s in stages}
    # the program's own clock and the profiler's agree on each stage
    m = {k: v["value"] for k, v in want["metrics"].items()}
    for stage in ("init_state", "unalias", "dyn_params", "dispatch"):
        assert m[f"host_ms.{stage}"] == pytest.approx(
            dur_ms[f"sim/{stage}"], abs=0.05)
    # the stages before the scan are the host time timed around the call
    assert m["engine_host_ms_per_job"] == pytest.approx(
        sum(dur_ms[f"sim/{k}"] for k in (
            "init_state", "unalias", "dyn_params", "lookup")), rel=0.05)
    # every idle gap wholly inside bench/run lies under an engine stage
    (brun,) = [s for s in spans if s.name == "run"]
    (job,) = [s for s in spans if s.name == "job"]
    inside = [n for n, g, t in sp.idle_gaps
              if brun.start_ns <= job.start_ns + t * 1e9
              and job.start_ns + (t + g) * 1e9 <= brun.end_ns]
    assert inside and all(n.startswith("sim/") for n in inside)


def test_engine_readers_give_nothing_for_another_job():
    ctx = dict(job=types.SimpleNamespace(seed=-1))
    for name in ENGINE_METRICS:
        assert spec.metric_reader(name)(ctx) is None, name


def test_engine_readers_read_the_traced_job(tmp_path, monkeypatch):
    """A traced tiny case1b run on the CPU reports every engine metric;
    the stages before the device scan fit in the host time the harness
    measures around the call, and set-up's counts exclude the traced
    job."""
    from repro.core import Simulation
    from repro.obs import hostspans

    monkeypatch.setattr(Simulation, "_compiled_cache", {})
    Simulation.reset_stats()
    root, bdir = tk.tiny_bench(tmp_path)
    rc, result, err = tk.run_bench(
        ["--workload", "case1b.seeds", "--seed", "4294967311",
         "--seconds", "1", "--trace", "1"], root, bdir)
    assert rc == 0, err
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(ENGINE_METRICS) <= set(m)
    rec = hostspans.last()
    for stage in ("init_state", "unalias", "dyn_params", "dispatch"):
        assert m[f"host_ms.{stage}"] == rec.seconds[f"sim/{stage}"] * 1e3
    before_scan = (m["host_ms.init_state"] + m["host_ms.unalias"]
                   + m["host_ms.dyn_params"]
                   + rec.seconds["sim/lookup"] * 1e3)
    assert 0 < before_scan <= m["engine_host_ms_per_job"]
    assert rec.compiles == {}
    stats = Simulation.stats()
    assert (stats["runs"], stats["program_compiles"]) == (2, 1)
    assert m["setup_backend_compiles"] == stats["backend_compiles"] >= 1
    assert m["setup_compile_s"] == stats["compile_s"] > 0


def test_last_compiled_is_the_program_the_benchmark_reads(monkeypatch):
    from cnsbench import build, jobs as jobsmod
    from repro.core import Simulation

    monkeypatch.setattr(Simulation, "_compiled_cache", {})
    run = tk.load_run_module()
    run.PLATFORM = "cpu"
    cfg = tk.tiny_config("case1b")
    traffic = {"kind": "solo"}
    sim = build.build(cfg, traffic)
    jobsmod.Jobs(sim, traffic, 7).run(0)
    assert sim.last_compiled.as_text() == run.program_text(sim)

"""The benchmark harness on the CPU: files found by name, the job loop
and its checks, the refusal without a chip, and the trace reduction."""
from __future__ import annotations

import gzip
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import bench_testkit as tk
from cnsbench import build, jobs as jobsmod, spec, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
DOC = json.loads((tk.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_cell_files_load_and_build(cell):
    c = spec.load_cell(cell, tk.ROOT)
    sim = build.build(c.config, c.traffic)
    assert sim.params.n_ticks > 0
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    names = {m["name"] for m in c.end_to_end}
    assert {"setup_s", "sim_s_per_s"} <= names and c.per_layer


@pytest.mark.parametrize("name", ["case1b", "sockshop-hs"])
def test_every_configuration_builds(name):
    cfg = json.loads((tk.BENCH_DIR / "configs" / f"{name}.json").read_text())
    for traffic in ("seeds", "u300"):
        t = json.loads((tk.BENCH_DIR / "traffic" / f"{traffic}.json")
                       .read_text())
        if name == "case1b" and traffic == "u300":
            continue
        assert build.build(cfg, t).caps.max_requests > 0


def test_capacity_sizes_match_the_configuration_file():
    cfg = json.loads((tk.BENCH_DIR / "configs" / "case1b.json").read_text())
    z = build.capacity_sizes(cfg["table2"])
    for k, v in cfg["sizes"].items():
        assert z[k] == v, k


def test_tiny_job_loop_runs_and_checks(tmp_path):
    root, bdir = tk.tiny_bench(tmp_path)
    rc, res, err = tk.run_bench(
        ["--workload", "case1b.seeds", "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"], root, bdir)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"sim_s_per_s", "peak_hbm_mb", "setup_s"}
    assert res["metrics"]["sim_s_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["admitted_ticks_differing"]["value"] == 0
    last = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in last)


def test_job_invariants_catch_a_lost_cloudlet(tmp_path):
    root, bdir = tk.tiny_bench(tmp_path)
    c = spec.load_cell("case1b.seeds", root, bdir)
    sim = build.build(c.config, c.traffic)
    res = sim.run(seed=1)
    assert jobsmod._invariants(res.state, sim.params.num_limit) == []
    ctr = res.state.counters._replace(finished=res.state.counters.finished
                                      - 1)
    bad = res.state._replace(counters=ctr)
    assert jobsmod._invariants(bad, sim.params.num_limit)


def test_job_seeds_are_fixed_by_the_run_seed():
    a = [jobsmod.job_seed(2 ** 31 + 11, k) for k in range(4)]
    assert a == [jobsmod.job_seed(2 ** 31 + 11, k) for k in range(4)]
    assert len(set(a)) == 4 and all(0 <= s < 2 ** 31 for s in a)


def test_refuses_to_measure_without_a_tpu(tmp_path):
    root, bdir = tk.tiny_bench(tmp_path)
    rc, res, _ = tk.run_bench(
        ["--workload", "case1b.seeds", "--seed", "1", "--seconds", "1",
         "--trace", "0"], root, bdir, platform="tpu")
    assert rc not in (0, None) and res is None


def test_bare_benchmark_directory_exits_non_zero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    (no program) prints no result."""
    shutil.copy(tk.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(tk.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "case1b.seeds",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_traffic_is_added_by_one_file(tmp_path):
    """sockshop-hs.sweep8, the Fig 11 sweep as one run_batch job, is
    added by a traffic file and a BENCHMARK.json entry alone."""
    bdir = tmp_path / "bench"
    shutil.copytree(tk.BENCH_DIR, bdir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    loads = [200, 328, 457, 585, 714, 842, 971, 1100]
    (bdir / "traffic" / "sweep8.json").write_text(json.dumps(dict(
        name="sweep8", kind="batch",
        points=[dict(n_clients=n, spawn_rate=n / 30.0) for n in loads])))
    doc = json.loads(json.dumps(DOC))
    doc["workloads"].append(dict(name="sockshop-hs.sweep8",
                                 config="sockshop-hs", traffic="sweep8",
                                 chips=1, why="sweep"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    c = spec.load_cell("sockshop-hs.sweep8", tmp_path, bdir)
    jobs = jobsmod.Jobs(build.build(c.config, c.traffic), c.traffic, 5)
    assert [p.n_clients for p in jobs.points] == loads
    assert jobs.sim_s == pytest.approx(8 * 60.0)


def test_phase_of_an_hlo_instruction():
    text = "\n".join([
        "HloModule jit_run_fn, is_scheduled=true",
        "%fused_computation.3 (param_0: f32[8]) -> f32[8] {",
        '  ROOT %mul.1 = f32[8]{0} multiply(%a, %b), metadata={op_name='
        '"jit(run_fn)/while/body/closed_call/Response/mul"}',
        "}",
        "ENTRY %main.9 (p: f32[8]) -> f32[8] {",
        '  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(run_fn)/while/body/closed_call/Execute/'
        'mul" stack_frame_id=3}',
        '  %fusion.8 = f32[8]{0} fusion(%p), kind=kLoop, '
        'calls=%fused_computation.3',
        '  %scatter.2 = s32[9]{0} scatter(%a), metadata={op_name='
        '"jit(run_fn)/while/body/Generation/jit(_normal)/add"}',
        '  %copy.1 = s32[9]{0} copy(%a)',
        '  ROOT %add.3 = f32[] add(%x, %y), metadata={op_name='
        '"jit(run_fn)/add"}',
        "}",
    ])
    assert trace.module_name(text) == "jit_run_fn"
    assert trace.phase_map(text) == {
        "mul.1": "Response", "fusion.7": "Execute", "fusion.8": "Response",
        "scatter.2": "Generation", "copy.1": trace.UNATTRIBUTED,
        "add.3": trace.UNATTRIBUTED}
    assert trace.instruction_name(
        "%fusion.7 = f32[8]{0:T(128)} fusion(f32[8] %p), kind=kLoop") \
        == "fusion.7"


def test_reduction_of_a_synthetic_trace():
    ms = 1e6
    op = trace.DeviceOp
    ops = [op("while.1", 1 * ms, 4 * ms),       # spans the loop's ops
           op("fusion.1", 1 * ms, 2 * ms),
           op("fusion.2", 3 * ms, 1 * ms),
           op("copy.3", 6 * ms, 1 * ms),        # another program's op
           op("fusion.1", 12 * ms, 5 * ms)]     # past the window
    modules = [op("jit_run_fn", 0.5 * ms, 5 * ms),
               op("jit_convert", 5.5 * ms, 2 * ms)]
    spans = [trace.Span("job", 0, 10 * ms), trace.Span("run", 0, 8 * ms),
             trace.Span("readback", 8 * ms, 2 * ms)]
    red = trace.reduce_trace(
        {"/device:TPU:0": trace.DevicePlane(ops, modules)}, spans,
        {"fusion.1": "Execute", "fusion.2": "Response"}, "jit_run_fn")
    assert red.window_s == pytest.approx(0.010)
    assert red.busy_s == pytest.approx(0.004)        # [1,4) + [6,7)
    assert red.idle_share == pytest.approx(0.6)
    assert red.n_ops == 3
    assert red.phase_s == pytest.approx(
        {"Execute": 0.002, "Response": 0.001, trace.OTHER_PROGRAMS: 0.001})
    assert red.idle_gaps[0] == ("readback", pytest.approx(0.003))
    assert red.top_ops[0] == ("Execute/fusion.1", pytest.approx(0.002))


def test_reduction_reproduces_the_recorded_chip_trace():
    """A tiny case1b job traced on a v5e (``bench/tools/record_trace.py``):
    the reduction gives the numbers the run printed there, and its parts
    add up."""
    want = json.loads((DATA / "result.json").read_text())
    devices, spans = trace.read_xplane(str(DATA / "job.xplane.pb.gz"))
    with gzip.open(DATA / "module.hlo.txt.gz", "rt") as f:
        text = f.read()
    red = trace.reduce_trace(devices, spans, trace.phase_map(text),
                             trace.module_name(text))
    assert want["device"]["platform"] == "tpu" and len(devices) == 1
    assert 0 < red.busy_s < red.window_s
    # leaf ops never overlap: their times add up to the busy time
    assert sum(red.phase_s.values()) == pytest.approx(red.busy_s)
    assert {"Generation", "Dispatch", "Execute"} <= set(red.phase_s)
    assert red.busy_s == pytest.approx(want["device"]["busy_s"], rel=1e-12)
    assert red.window_s == pytest.approx(want["device"]["window_s"],
                                         rel=1e-12)
    ticks = build.capacity_sizes(tk.tiny_config("case1b")["table2"])[
        "n_ticks"]
    ctx = dict(reduction=red, ticks=ticks, n_devices=len(devices))
    for name, m in want["metrics"].items():
        if name.startswith(("phase_ms.", "device_")):
            got = spec.metric_reader(name)(ctx)
            assert got == pytest.approx(m["value"], rel=1e-12), name
    assert [[n, s] for n, s in red.top_ops[:9]] == [
        [n, pytest.approx(s, rel=1e-12)]
        for n, s in want["breakdown"]["device_ops"][:9]]


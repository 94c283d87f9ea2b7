"""Helpers of the benchmark's tests: a small copy of the benchmark (one
case1b cell at a size a CPU test can hold) and a way to drive
``bench/run.py`` on it without a chip."""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import shutil
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# case1b's Table 2 counts cut to a CPU test: 2,000 requests on 10
# replicas, 122 ticks (the sizing arithmetic is the configuration's own)
TINY_TABLE2 = dict(n_requests=2000, replicas=10, target_ticks=50)
# SockShop-HS cut to 15 simulated seconds: one autoscaling event
TINY_SOCKSHOP_TICKS = 150


def tiny_config(name: str) -> dict:
    """A configuration cut to a size a CPU test can hold."""
    cfg = json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())
    if name == "case1b":
        cfg["table2"].update(TINY_TABLE2)
    else:
        cfg["params"]["n_ticks"] = TINY_SOCKSHOP_TICKS
    return cfg


def load_run_module():
    from cnsbench import spec
    return spec.load_module(BENCH_DIR / "run.py")


def tiny_bench(tmp: pathlib.Path, cell: str = "case1b.seeds") -> tuple:
    """(root, bench_dir) of a copy of the benchmark that holds the one
    cell ``cell``, its configuration cut by ``tiny_config``."""
    root = tmp / "checkout"
    bdir = root / "bench"
    (bdir / "configs").mkdir(parents=True)
    shutil.copytree(BENCH_DIR / "traffic", bdir / "traffic")
    shutil.copytree(BENCH_DIR / "metrics", bdir / "metrics")
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["workloads"] = [w for w in doc["workloads"] if w["name"] == cell]
    name = doc["workloads"][0]["config"]
    (bdir / "configs" / f"{name}.json").write_text(
        json.dumps(tiny_config(name)))
    shutil.copy(BENCH_DIR / "configs" / f"{name}.reference.py",
                bdir / "configs" / f"{name}.reference.py")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root, bdir


def run_bench(argv, root, bdir, platform="cpu"):
    """``bench/run.py`` main on the copy, with the device check looking
    for ``platform``; returns (exit code, result dict or None, stderr)."""
    run = load_run_module()
    run.PLATFORM = platform
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        try:
            rc = run.main(argv, root=root, bench_dir=bdir, cache=False)
        except SystemExit as e:
            rc = e.code
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if lines else None
    return rc, result, err.getvalue()

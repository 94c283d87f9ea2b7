#!/usr/bin/env python3
"""Record the small trace the trace-reduction test reads.

    python3 bench/tools/record_trace.py OUT_DIR

Runs the traced path of ``bench/run.py`` on case1b cut to a CPU test's
size (``bench/tests/bench_testkit.tiny_config``) on the machine's
accelerator, and writes into OUT_DIR the trace (``job.xplane.pb.gz``),
the program's optimised HLO text (``module.hlo.txt.gz``) and the run's
result line (``result.json``).  Needs a TPU, like every measurement.
"""
from __future__ import annotations

import gzip
import json
import os
import pathlib
import shutil
import sys
import tempfile

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR / "tests"))

import bench_testkit as tk  # noqa: E402


def main(out: str) -> int:
    out_dir = pathlib.Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="cnsbench-record-"))
    try:
        root, bdir = tk.tiny_bench(tmp)
        os.environ["CNSBENCH_KEEP_TRACE"] = str(tmp / "keep")
        rc, result, err = tk.run_bench(
            ["--workload", "case1b.seeds", "--seed", "2718281828",
             "--seconds", "1", "--trace", "1"], root, bdir, platform="tpu")
        sys.stderr.write(err)
        if rc != 0 or result is None:
            return rc or 1
        for name in ("job.xplane.pb", "module.hlo.txt"):
            with open(tmp / "keep" / name, "rb") as f, \
                    gzip.open(out_dir / (name + ".gz"), "wb") as g:
                shutil.copyfileobj(f, g)
        (out_dir / "result.json").write_text(json.dumps(result, indent=1))
        print(json.dumps(result))
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

#!/usr/bin/env python3
"""Readings of a cell's control: its configuration's plain reference
computed in bfloat16 (the precision below the configuration's float32),
put in the program's place and compared with the float32 reference by the
numbers that decide ``correct``.

    python3 bench/tools/control_readings.py WORKLOAD SEED [SEED ...]

Prints one JSON line per seed with each compared number and its limit.
The reference runs on the host's CPU; no accelerator is needed.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import ml_dtypes

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

from cnsbench import spec  # noqa: E402


def main(workload: str, seeds) -> int:
    cell = spec.load_cell(workload, BENCH_DIR.parent)
    ref = spec.reference_module(cell.config)
    for seed in seeds:
        t0 = time.perf_counter()
        want = ref.run(cell.config, cell.traffic, seed)
        got = ref.run(cell.config, cell.traffic, seed, ml_dtypes.bfloat16)
        gaps = ref.gaps(want, got)
        print(json.dumps(dict(
            workload=workload, seed=seed,
            seconds=time.perf_counter() - t0,
            numbers={k: dict(value=v, limit=ref.LIMITS[k])
                     for k, v in gaps.items()},
            fails=[k for k, v in gaps.items() if v > ref.LIMITS[k]])),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], [int(s) for s in sys.argv[2:]]))

#!/usr/bin/env python3
"""Chip benchmark of the detection gateway: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's gateway (`BatchingScheduler` over the program in
`src/`), makes its data from the seed, warms up every shape the window
uses, drives the cell's traffic for `--seconds`, drains, checks every
delivered verdict against the plain reference, and prints one JSON
object as the last line of standard output.  `--trace 1` runs the same
traffic with the profiler on over a stretch in the middle of the window
and reports the cell's per-layer metrics.  Exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell needs.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench.lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))

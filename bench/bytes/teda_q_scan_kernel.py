"""Useful HBM bytes of the Q-format TEDA kernel, counted from the work
a window retired, not from the (T, C) block that was launched.

Per verdict: the input sample (int32 after quantization) and what the
gateway delivers of it: the outlier flag (int8), plus the Q
eccentricity (int32) when the configuration keeps it (`collect`).  Per
slot and call, for each slot that retired at least one sample: the
slot's registers (sample count, mean, variance: int32 each), read once
and written once.  Padding lanes and suspended slots add nothing.
"""
from __future__ import annotations

#: the kernel's operation in a device trace: the program's jitted
#: wrapper around the `pallas_call` (a custom call, one per call)
TRACE_OP = "%_padded_q_call"

I32 = 4
I8 = 1


def per_verdict(cfg: dict) -> int:
    return I32 + I8 + (I32 if cfg["collect"] else 0)


def per_slot_call(cfg: dict) -> int:
    return 2 * 3 * I32


def useful_bytes(cfg: dict, samples: int, slot_calls: int) -> int:
    return samples * per_verdict(cfg) + slot_calls * per_slot_call(cfg)

"""Data: DAMADICS-like fault streams."""
from repro.data.damadics import (TABLE2, FaultWindow, base_signals,
                                 detection_report, inject, make_benchmark)

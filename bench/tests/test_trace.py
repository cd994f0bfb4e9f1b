"""The reduction from a profiler trace to device busy and idle time,
time per operation, and idle gaps labelled by the host span they fall
in: on a hand-built trace, and on a small trace recorded on the chip."""
import gzip
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench.lib import xplane
from bench.lib.harness import BENCH, load_module


def _ev(name, start_ns, dur_ns):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns)


def _prof(offset_ns):
    """Host clock t (s) sits at t * 1e9 + offset_ns on the trace clock."""
    ops = [_ev("fusion.1", 1.00e9, 0.10e9),          # 1.00 .. 1.10
           _ev("my_scan_kernel", 1.05e9, 0.20e9),    # overlaps: .. 1.25
           _ev("copy.2", 1.50e9, 0.10e9),            # 1.50 .. 1.60
           _ev("fusion.1", 2.50e9, 0.10e9)]          # outside the stretch
    ops = [_ev(e.name, e.start_ns + offset_ns, e.duration_ns) for e in ops]
    host = [_ev("step", 0.95e9 + offset_ns, 0.50e9),
            _ev("step", 1.45e9 + offset_ns, 0.50e9)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=ops),
            NS(name="XLA Modules", events=[_ev("jit_x", 0, 5e9)])]),
        NS(name="/host:CPU", lines=[NS(name="python", events=host)])])


def test_reduce_by_hand():
    spans = [("step", 0.95, 1.45), ("step", 1.45, 1.95),
             ("retire", 1.30, 1.40), ("gen", 1.62, 1.70)]
    red = xplane.reduce(_prof(offset_ns=7.5e9), 1.0, 2.0, spans)
    assert red["window_s"] == pytest.approx(1.0)
    # busy: union of [1.00, 1.25] and [1.50, 1.60]
    assert red["busy_s"] == pytest.approx(0.35)
    assert red["by_op"]["my_scan_kernel"] == pytest.approx(0.20)
    assert red["by_op"]["fusion.1"] == pytest.approx(0.10)
    # idle [1.25, 1.50]: retire holds 1.30-1.40, the steps the rest;
    # idle [1.60, 2.00]: gen 1.62-1.70, step to 1.95, then no span
    assert red["idle_by_span"] == pytest.approx(
        {"step": 0.15 + 0.27, "retire": 0.10, "gen": 0.08,
         "outside spans": 0.05})
    assert xplane.top(red["by_op"], 1) == [["my_scan_kernel",
                                            pytest.approx(0.2)]]


def test_reduce_needs_the_anchor_spans():
    with pytest.raises(ValueError, match="no 'step' annotation"):
        xplane.reduce(_prof(0.0), 1.0, 2.0, [("gen", 1.0, 2.0)])


# A 3 s traced stretch of `linerate-q.replay` (seed 203, TPU v5 lite,
# one chip), recorded by `bench/run.py --trace 1 --keep-trace` and
# gzipped: 62 scheduler ticks of full (4096, 256) frames.
RECORDED = Path(__file__).parent / "data" / "linerate-q.replay.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with gzip.open(RECORDED) as f:
        prof = ProfileData.from_serialized_xspace(f.read())
    # the harness's annotations, on the trace's own clock: offset 0
    spans = [(name, a * 1e-9, b * 1e-9)
             for name in ("step", "gen", "dispatch")
             for a, b in xplane.host_events(prof, name)]
    steps = sorted(s for s in spans if s[0] == "step")
    return prof, spans, steps[0][1], steps[-1][2]


def test_recorded_trace_reduces(recorded):
    prof, spans, t0, t1 = recorded
    assert len([s for s in spans if s[0] == "step"]) == 62
    red = xplane.reduce(prof, t0, t1, spans)
    assert red["window_s"] == pytest.approx(3.015161080)
    assert red["busy_s"] == pytest.approx(0.056784896, rel=1e-6)
    # the Q kernel is the program's custom call, one per frame
    q = load_module(BENCH / "bytes" / "teda_q_scan_kernel.py")
    kernel = {k: v for k, v in red["by_op"].items()
              if k.startswith(q.TRACE_OP)}
    assert list(kernel) == ["%_padded_q_call.1"]
    assert kernel["%_padded_q_call.1"] == pytest.approx(0.054644486,
                                                        rel=1e-6)
    # every idle instant is given to exactly one host span
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert max(red["idle_by_span"], key=red["idle_by_span"].get) == "step"


def test_recorded_busy_is_the_union_of_device_ops(recorded):
    prof, spans, t0, t1 = recorded
    ops = xplane.device_ops(prof)
    assert len(ops) == 5398
    # device ops of one TPU core do not overlap: the union is the sum
    inside = [(max(a, t0 * 1e9), min(b, t1 * 1e9)) for _, a, b in ops
              if b > t0 * 1e9 and a < t1 * 1e9]
    total = sum(b - a for a, b in inside) * 1e-9
    red = xplane.reduce(prof, t0, t1, spans)
    assert red["busy_s"] == pytest.approx(total, rel=1e-6)

"""The readers of the scheduler's phase spans, on hand-built span
lists, and on a tiny traced run of the replay cell on the CPU."""
import pytest

from bench.lib.harness import BENCH, Layer, load_module
from bench.tests.tiny import run_tiny

PHASE_METRICS = ("assemble_ms", "account_ms", "admission_ms",
                 "tick_self_ms", "d2h_bytes_per_verdict",
                 "compiles_in_window")


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py").read


def layer(spans, ticks=2, verdicts=0):
    """A `Layer` holding only program spans (name, start, end, args),
    times in ms."""
    ctx = Layer.__new__(Layer)
    ctx.program_spans = [(n, a * 1e-3, b * 1e-3, args)
                         for n, a, b, args in spans]
    ctx.ticks = ticks
    ctx.work = [verdicts, 0]
    return ctx


# two ticks of 10 ms; in the first, `acquire` nests in `admission` and
# `retire` in `flush`; in the second a compile overlaps `dispatch`, and
# the first tick's call is fetched
TWO_TICKS = [
    ("tick", 0, 10, {"tick": 1}),
    ("admission", 1, 4, {"admitted": 1}),
    ("acquire", 2, 3, {"n": 1}),
    ("flush", 5, 8, {}),
    ("dispatch", 4, 5, {"tick": 1, "samples": 1000, "h2d_bytes": 9}),
    ("retire", 6, 7, {"dispatch_tick": 0, "d2h_bytes": 600}),
    ("assemble", 8, 9, {}),
    ("tick", 10, 20, {"tick": 2}),
    ("assemble", 10, 12, {}),
    ("dispatch", 12, 15, {"tick": 2, "samples": 700, "h2d_bytes": 9}),
    ("compile", 13, 16, {}),
    ("retire", 17, 18, {"dispatch_tick": 1, "d2h_bytes": 1400}),
    ("account", 18, 19.5, {}),
]


def test_tick_self_counts_nested_children_once():
    # tick 1: 10 - (3 + 1 + 3 + 1) = 2; tick 2: 10 - (2 + 4 + 1 + 1.5)
    assert reader("tick_self_ms")(layer(TWO_TICKS)) == pytest.approx(1.75)


def test_tick_self_clips_children_to_their_tick():
    spans = [("tick", 0, 10, {}), ("flush", 8, 14, {}),
             ("tick", 12, 20, {}), ("retire", 19, 25, {})]
    # tick 1: 10 - 2; tick 2: 8 - (2 + 1)
    assert reader("tick_self_ms")(layer(spans)) == pytest.approx(6.5)


def test_phase_times_per_tick():
    ctx = layer(TWO_TICKS)
    assert reader("assemble_ms")(ctx) == pytest.approx(1.5)
    assert reader("account_ms")(ctx) == pytest.approx(0.75)
    assert reader("admission_ms")(ctx) == pytest.approx(1.5)


def test_bytes_per_verdict_and_compiles():
    ctx = layer(TWO_TICKS)
    # only tick 1's call was both dispatched and fetched in the stretch
    assert reader("d2h_bytes_per_verdict")(ctx) == pytest.approx(1.4)
    assert reader("compiles_in_window")(ctx) == 1
    quiet = [s for s in TWO_TICKS if s[0] not in ("admission", "acquire",
                                                   "compile")]
    assert reader("compiles_in_window")(layer(quiet)) == 0
    assert reader("admission_ms")(layer(quiet)) == 0.0


def test_a_program_without_tick_spans_reads_nothing():
    """The parent of these spans recorded `dispatch`, `retire` and
    `flush` only: every new reader returns None there."""
    old = [("dispatch", 1, 4, {}), ("retire", 5, 6, {}),
           ("flush", 5, 7, {})]
    for name in PHASE_METRICS:
        assert reader(name)(layer(old, verdicts=100)) is None, name


def test_traced_replay_reads_every_phase_metric():
    res = run_tiny("linerate-q.replay", trace=True, seconds=3.0)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    for name in PHASE_METRICS:
        assert f"{name}.replay" in m, name
    # collect is off: one int8 flag per verdict comes back
    assert m["d2h_bytes_per_verdict.replay"] == pytest.approx(1.0,
                                                              abs=0.01)
    assert m["compiles_in_window.replay"] == 0
    assert {"step", "tick"}.isdisjoint(
        name for name, _ in res["breakdown"]["idle_gaps"][:1])

"""The `release_ms` reader on hand-built span lists, as the other phase
readers in `test_phases.py`, and on a tiny traced run of the replay
cell on the CPU."""
import pytest

from bench.tests.test_phases import TWO_TICKS, layer, reader
from bench.tests.tiny import run_tiny


def test_release_time_per_tick():
    """`release` spans nest in `complete`, as `acquire` in `admission`;
    a stretch without one reads 0."""
    spans = TWO_TICKS + [("complete", 19.5, 19.9, {"completed": 2}),
                         ("release", 19.5, 19.7, {"n": 1}),
                         ("release", 19.7, 19.9, {"n": 1})]
    assert reader("release_ms")(layer(spans)) == pytest.approx(0.2)
    assert reader("release_ms")(layer(TWO_TICKS)) == 0.0


def test_release_reads_nothing_without_tick_spans():
    old = [("dispatch", 1, 4, {}), ("retire", 5, 6, {}),
           ("flush", 5, 7, {})]
    assert reader("release_ms")(layer(old, verdicts=100)) is None


def test_release_reads_nothing_from_a_pool_without_release_spans(
        monkeypatch):
    """A program whose `SlotPool` records no `release` reads nothing,
    not 0, in a stretch with or without a completion."""
    from repro.engine.pool import SlotPool
    monkeypatch.setattr(SlotPool, "SPANS", ("acquire",))
    done = TWO_TICKS + [("complete", 19.5, 19.9, {"completed": 2})]
    for spans in (TWO_TICKS, done):
        assert reader("release_ms")(layer(spans)) is None
    monkeypatch.delattr(SlotPool, "SPANS")
    assert reader("release_ms")(layer(TWO_TICKS)) is None


def test_traced_replay_reads_release_ms():
    res = run_tiny("linerate-q.replay", trace=True, seconds=3.0)
    assert res["correct"], res["checks"]
    assert "release_ms.replay" in res["metrics"]

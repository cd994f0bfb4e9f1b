"""A run whose timed path is broken underneath comes out not correct:
once for each fault a one-chip cell can have (there is no exchange
between chips to leave out)."""
import numpy as np
import pytest

from bench.tests.tiny import run_tiny
from repro.engine import SlotPool
from repro.engine.engine import StreamEngine

CELLS = ("fleet-ens5.live", "linerate-q.replay")


def state_unchanged(monkeypatch):
    """Every call returns its verdicts but leaves the state as it was."""
    real = StreamEngine.process

    def process(self, x, active=None, valid_lens=None):
        before = self.state
        out = real(self, x, active=active, valid_lens=valid_lens)
        self.state = before
        return out

    monkeypatch.setattr(StreamEngine, "process", process)


def half_batch_left_out(monkeypatch):
    """The upper half of the slots is suspended in every call."""
    real = SlotPool.process

    def process(self, x, active=None, valid_lens=None):
        v = np.array(valid_lens)
        v[v.shape[0] // 2:] = 0
        return real(self, x, active=active, valid_lens=v)

    monkeypatch.setattr(SlotPool, "process", process)


def answer_altered(monkeypatch):
    """One verdict of every call is flipped where it is produced."""
    real = SlotPool.process

    def process(self, x, active=None, valid_lens=None):
        out = dict(real(self, x, active=active, valid_lens=valid_lens))
        slot = int(np.flatnonzero(np.asarray(valid_lens))[0])
        out["outlier"] = out["outlier"].at[0, slot].set(
            ~out["outlier"][0, slot].astype(bool))
        return out

    monkeypatch.setattr(SlotPool, "process", process)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   answer_altered])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run_tiny(name, seconds=1.0)
    assert not res["correct"], res["checks"]

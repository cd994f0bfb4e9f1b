"""The `fleet-ens5-16k` cells on the CPU at a tiny size: both drive end
to end through the harness and pass their checks; a fault planted in a
moment member, in the vote, or in the timed path fails them; the
control fails their limits; a traced run reads the new metrics that
need no device trace."""
import time

import numpy as np
import pytest

from bench.lib.harness import Cell, run_cell
from bench.tests.test_faults import (answer_altered, half_batch_left_out,
                                     state_unchanged)
from repro.engine import SlotPool

CELLS = ("fleet-ens5-16k.live", "fleet-ens5-16k.backfill")


def tiny_cell(name: str) -> Cell:
    cell = Cell(name)
    p = cell.wl["params"]
    p.update(warm_seconds=0.3, drain_seconds=30.0)
    cell.wl["trace_seconds"] = 0.5
    cell.cfg.update(streams=128, buckets=[128], history=16, chunk_t=16,
                    rate_hz=20.0)
    if cell.wl["loop"] == "backfill":
        p.update(recordings=8, gap=600, backlog=32)
    return cell


def run_tiny(name: str, *, trace: bool = False, seconds: float = 1.5,
             seed: int = 2 ** 33 + 5, control: bool = False) -> dict:
    return run_cell(tiny_cell(name), seed, seconds, trace,
                    time.perf_counter(), control=control, interpret=True)


@pytest.mark.parametrize("name", CELLS)
def test_cell_drives_and_passes_its_checks(name):
    res = run_tiny(name, control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"verdicts_per_s", "setup_s"}
    assert res["checks"]["exact_mismatch"]["value"] == 0
    # the control (bfloat16 moments) fails a limit of the moment members
    ctl = res["control"]
    assert any(ctl[k]["value"] > ctl[k]["limit"]
               for k in ("flag_disagree", "score_gap")), ctl


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_the_new_metrics(name):
    res = run_tiny(name, trace=True, seconds=3.0)
    assert res["correct"], res["checks"]
    suffix = name.rsplit(".", 1)[1]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    for metric in ("members_ms", "step_ms", "account_ms", "assemble_ms",
                   "retire_ms", "device_idle", "d2h_bytes_per_verdict",
                   "compiles_in_window"):
        assert f"{metric}.{suffix}" in m, metric
    # `ensemble_scan_roofline` reads the kernel's op in a device trace:
    # an interpreted kernel has none
    assert ("gen_lag_p95_ms.live" in m) == (suffix == "live")
    assert ("dispatch_ms.backfill" in m) == (suffix == "backfill")
    assert 0 < m[f"members_ms.{suffix}"] <= m[f"account_ms.{suffix}"]


def _altered(monkeypatch, alter):
    real = SlotPool.process

    def process(self, x, active=None, valid_lens=None):
        out = dict(real(self, x, active=active, valid_lens=valid_lens))
        live = np.flatnonzero(np.asarray(valid_lens))
        return alter(out, live)

    monkeypatch.setattr(SlotPool, "process", process)


def rde_flag_flipped(monkeypatch):
    """The rde member's flag (bit 1) is inverted on every live slot."""
    def alter(out, live):
        out["ecc"] = out["ecc"].at[:, live].set(out["ecc"][:, live] ^ 2)
        return out
    _altered(monkeypatch, alter)


def teda_score_scaled(monkeypatch):
    """The teda member's score stream is 50% high."""
    def alter(out, live):
        out["scores"] = out["scores"].at[0].multiply(1.5)
        return out
    _altered(monkeypatch, alter)


def vote_flipped(monkeypatch):
    """The first live slot's first vote of every call is inverted."""
    def alter(out, live):
        out["outlier"] = out["outlier"].at[0, live[0]].set(
            ~out["outlier"][0, live[0]].astype(bool))
        return out
    _altered(monkeypatch, alter)


@pytest.mark.parametrize("fault", [rde_flag_flipped, teda_score_scaled,
                                   vote_flipped, state_unchanged,
                                   half_batch_left_out, answer_altered])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run_tiny(name, seconds=1.0)
    assert not res["correct"], res["checks"]

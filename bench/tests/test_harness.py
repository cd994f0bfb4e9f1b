"""CPU rehearsal of the harness: due-time stamping and latency
arithmetic, the closed-loop top-up, the result line's shape, and the
refusal to measure without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.lib.harness import BENCH, ROOT, load_module
from bench.tests.tiny import run_tiny

OPEN = load_module(BENCH / "traffic" / "open_loop.py")
CLOSED = load_module(BENCH / "traffic" / "closed_loop.py")
CELLS = ("fleet-ens5.live", "linerate-q.replay")


def test_due_count_matches_due_times():
    rng = np.random.default_rng(3)
    rate, n, epoch = 4.0, 50, 100.0
    phase = rng.uniform(0, 1, n) / rate
    due = OPEN.due_times(epoch, phase, rate, 12)
    ph_sorted = np.sort(phase)
    for t in np.linspace(epoch - 1, epoch + 2.9, 97):
        assert OPEN.due_count(t, epoch, rate, ph_sorted) == \
            int((due <= t).sum())


def test_window_stats_counts_drained_and_lost_samples():
    # three streams, four samples each, due at 0, 1, 2, 3 (+ phase)
    due = OPEN.due_times(0.0, np.array([0.0, 0.25, 0.5]), 1.0, 4)
    deliv = due + 0.1
    deliv[2, 0] = 4.5          # due 2.0 in the window, drained at 4.5
    deliv[1, 1] = np.nan       # due 1.25 in the window, never delivered
    st = OPEN.window_stats(due, deliv, 1.0, 3.0)
    # due in [1, 3): 1.0, 1.25, 1.5, 2.0, 2.25, 2.5
    assert st["attempted"] == 6
    assert st["failed"] == 1
    # latencies of 1.0, 1.5, 2.0 (drained), 2.25, 2.5
    lat = np.array([0.1, 0.1, 2.5, 0.1, 0.1])
    assert st["verdict_p50_ms"] == pytest.approx(100.0)
    assert st["verdict_p95_ms"] == pytest.approx(
        np.percentile(lat, 95) * 1e3)
    # delivered inside [1, 3): 1.1, 1.6, 2.35, 2.6 -> 4 in 2 s
    # (1.25's never came, 2.0's came at 4.5)
    assert st["verdicts_per_s"] == pytest.approx(4 / 2.0)


class FakeSched:
    def __init__(self):
        self.fed, self.closed, self.submitted = {}, [], []

    def submit(self, req):
        self.submitted.append(req.rid)
        self.fed[req.rid] = 0
        return True

    def feed(self, rid, samples):
        assert rid not in self.closed
        self.fed[rid] += len(samples)

    def close(self, rid):
        self.closed.append(rid)


class Ev:
    def __init__(self, rid, n):
        self.rid, self.data = rid, {"n": n, "outlier": np.zeros(n, bool),
                                    "dispatch_tick": 1}


def test_top_up_keeps_backlog_and_rolls_recordings():
    store = load_module(BENCH / "reference" / "teda_q.py").Store()
    recs = np.arange(3000, dtype=np.float32).reshape(3, 1000)
    sched = FakeSched()
    cl = CLOSED.Client(sched, recs, 2, 700, store)
    cl.top_up()
    assert sched.closed == []
    assert sched.fed == {"c0p0": 700, "c1p0": 700}
    # 500 of channel 0 come back: it is topped up across the end of its
    # first recording, which closes, and the next one opens
    cl.absorb([(0.0, Ev("c0p0", 500))], {})
    cl.top_up()
    assert cl.fed_total[0] - cl.got_total[0] == 700
    assert sched.closed == ["c0p0"]
    assert sched.fed["c0p0"] == 1000 and sched.fed["c0p1"] == 200
    assert list(cl.fed_total) == [1200, 700]
    # nothing came back on channel 1: it is not topped up
    cl.top_up()
    assert cl.fed_total[1] == 700
    # channel c replays recording (c + pass) mod R
    assert cl.stream_of((0, 1)) == 1 and cl.stream_of((1, 1)) == 2
    assert cl.stream_of((2, 2)) == 1


def _shape(res, trace):
    keys = list(res)
    assert keys[:3] == ["correct", "attempted", "failed"]
    assert keys[-1] == "checks"
    dev = res["device"]
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in dev
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
    if trace:
        assert "busy_s" in dev and dev["window_s"] > 0
        bd = res["breakdown"]
        assert set(bd) == {"device_ops", "idle_gaps"}
        assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    json.dumps(res)


@pytest.mark.parametrize("name", CELLS)
def test_result_line(name):
    res = run_tiny(name)
    res.pop("_reference_s")
    _shape(res, trace=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {"verdicts_per_s", "setup_s"} | (
        {"verdict_p50_ms", "verdict_p95_ms"} if name.startswith("fleet")
        else set())
    assert set(res["metrics"]) == want


@pytest.mark.parametrize("name", CELLS)
def test_traced_result_line(name):
    res = run_tiny(name, trace=True, seconds=3.0)
    res.pop("_reference_s")
    _shape(res, trace=True)
    assert res["correct"], res["checks"]
    assert "step_ms." + ("fleet" if name.startswith("fleet")
                         else "replay") in res["metrics"]


def test_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "linerate-q.replay",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

"""The serving path held to counts, under the traffic of the benchmark's
cells cut to a size the CPU interprets in seconds.

Once a gateway is warm, a tick must compile nothing, slot admission and
release must not read the device's active mask back (one capacity
bucket, so no resize), and the bytes fetched from the device must be
exactly the call outputs the scheduler keeps: the (T, C) flags, and on
the ensemble (whose cells run with `collect` on) the (T, C) member
bitmask and the (K, T, C) member scores beside the vote.  These are
counts, not times, so they hold on any backend and any host.

Three traffic shapes, each after a cell:

  * `replay`  full (chunk_t, C) frames in a closed loop, every channel
              kept two frames ahead (`linerate-q.replay`);
  * `live`    one-sample slots at partial occupancy: 6 tenants in an
              8-slot bucket, their history replayed at admission, then
              half of them due each tick (`fleet-ens5-16k.live`);
  * `churn`   the replay with short recordings, so that every channel
              closes its request and opens the next on the same tick,
              mid-run (the replay's whole-bucket rollover).
"""
import functools

import numpy as np
import pytest

from helpers import active_fetches
from repro.fixedpoint import QFormat
from repro.launch.batching import BatchingScheduler, Request
from repro.obs import compile_watch

FMT = QFormat(32, 20)
C = 8              # the one capacity bucket
CHUNK_T = 8
DECODE_T = 1
# the fleet cells' five members (`bench/configs/fleet-ens5-16k.json`)
MEMBERS = ("teda", "rde", "zscore", "hst", "teda-q")
BACKENDS = ("scan", "pallas", "pallas-q", "ensemble")
TRAFFIC = ("replay", "live", "churn")
COUNTS = ("compiles", "fetches", "d2h_bytes")


def _scheduler(backend):
    opts = {"detectors": MEMBERS} if backend == "ensemble" else {}
    return BatchingScheduler(
        backend, fmt=FMT, buckets=(C,), chunk_t=CHUNK_T,
        decode_t=DECODE_T, m=3.0, queue_limit=2 * C,
        collect=backend == "ensemble", **opts)


def _fetched_bytes(backend, t):
    """What one call of t rows fetches: the bool flags, and on the
    ensemble the int32 member bitmask and float32 member scores."""
    flags = t * C
    if backend != "ensemble":
        return flags
    return flags + 4 * t * C + 4 * len(MEMBERS) * t * C


class _Replay:
    """A closed-loop client: each channel replays recordings of `length`
    samples one request each, two frames ahead of the scheduler; a
    finished recording is closed and the next opens at once."""

    def __init__(self, sched, length, rng):
        self.sched, self.length, self.rng = sched, length, rng
        self.pass_no = [0] * C
        self.at = [0] * C
        for c in range(C):
            self._open(c)
            self.feed(c)
            self.feed(c)

    def _open(self, c):
        assert self.sched.submit(Request(f"c{c}p{self.pass_no[c]}"))

    def feed(self, c):
        rid = f"c{c}p{self.pass_no[c]}"
        self.sched.feed(rid, self.rng.normal(size=CHUNK_T)
                        .astype(np.float32))
        self.at[c] += CHUNK_T
        if self.at[c] == self.length:
            self.sched.close(rid)
            self.pass_no[c] += 1
            self.at[c] = 0
            self._open(c)

    def tick(self, _):
        self.sched.step()
        for c in range(C):
            self.feed(c)


class _Live:
    """Six tenants in the bucket of eight, each admitted with a frame of
    history, then one sample each for the half of them due this tick."""

    def __init__(self, sched, rng):
        self.sched, self.rng = sched, rng
        for i in range(6):
            assert sched.submit(Request(
                f"t{i}", rng.normal(size=CHUNK_T).astype(np.float32)))

    def tick(self, i):
        for j in range(0, 6, 2):
            rid = f"t{(j + i) % 6}"
            if rid in self.sched.runs:
                self.sched.feed(rid, self.rng.normal(size=1)
                                .astype(np.float32))
        self.sched.step()


# (warm-up ticks, measured ticks, rows of every measured call)
SHAPES = {"replay": (3, 8, CHUNK_T), "live": (4, 8, DECODE_T),
          "churn": (6, 10, CHUNK_T)}


@functools.lru_cache(maxsize=None)
def _measure(backend, traffic):
    """Warm a gateway up, then count over the measured ticks: compiles,
    active-mask fetches, fetched bytes, calls, short-program ticks and
    completions.  In-flight calls are retired at both ends of the
    window, so the calls it fetches are the calls it dispatches."""
    sched = _scheduler(backend)
    rng = np.random.default_rng(17)
    if traffic == "live":
        client = _Live(sched, rng)
    else:
        # churn: a recording is 4 frames, so each channel rolls over
        # every 4 ticks, all on the same tick
        client = _Replay(sched, (4 if traffic == "churn" else 1 << 20)
                         * CHUNK_T, rng)
    warm, ticks, _ = SHAPES[traffic]
    for i in range(warm):
        client.tick(i)
    reg, lbl = sched.registry, {"sched": sched.name}

    def counts():
        return (reg.get("sched_d2h_bytes_total").labels(**lbl).value,
                active_fetches(reg), sched.completed,
                reg.get("sched_calls_total").labels(**lbl).value,
                sched.short_ticks)

    sched._flush()
    before = counts()
    mark = compile_watch().mark()
    for i in range(warm, warm + ticks):
        client.tick(i)
    sched._flush()
    compiles = compile_watch().since(mark)["compiles"]
    after = counts()
    got = dict(zip(("d2h_bytes", "fetches", "completed", "calls",
                    "short_ticks"),
                   (int(a - b) for a, b in zip(after, before))))
    got["compiles"] = compiles
    return got


@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("traffic", TRAFFIC)
@pytest.mark.parametrize("backend", BACKENDS)
def test_warm_tick_counts(backend, traffic, count):
    got = _measure(backend, traffic)
    _, ticks, t = SHAPES[traffic]
    # the traffic is what it claims: one call a tick, each on the
    # program of its shape, and the churn releases and re-admits
    assert got["calls"] == ticks
    assert got["short_ticks"] == (ticks if t == DECODE_T else 0)
    if traffic == "churn":
        assert got["completed"] >= 2 * C
    if count == "compiles":
        assert got["compiles"] == 0
    elif count == "fetches":
        assert got["fetches"] == 0
    else:
        assert got["d2h_bytes"] == ticks * _fetched_bytes(backend, t)

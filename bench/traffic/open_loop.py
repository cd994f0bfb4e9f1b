"""Open-loop traffic: every stream emits samples at the configuration's
fixed rate, each stream at its own seeded phase, and the generator
feeds each sample when it falls due, whether or not earlier verdicts
have come back.  Latency runs from a sample's due time to the delivery
of its verdict, so a stall shows in every sample it delays.

Parameters (the workload file's "params"):
  phase          "uniform": offsets uniform in one period, as jittered
                 scrape offsets spread a fleet
  warm_seconds   live traffic after the first live verdict and before
                 the window opens (set-up)
  drain_seconds  how long past the window a verdict may still come
"""
from __future__ import annotations

import math

import numpy as np


def _phases(kind: str, n: int, rate: float, rng) -> np.ndarray:
    if kind != "uniform":
        raise ValueError(f"unknown phase distribution {kind!r}")
    return rng.uniform(0.0, 1.0, size=n) / rate


def due_count(t: float, epoch: float, rate: float,
              ph_sorted: np.ndarray) -> int:
    """How many samples are due by time t: stream i's j-th sample is
    due at epoch + phase_i + j / rate."""
    u = (t - epoch) * rate
    if u < 0:
        return 0
    whole = math.floor(u)
    return len(ph_sorted) * whole + int(np.searchsorted(
        ph_sorted, (u - whole) / rate, side="right"))


def due_times(epoch: float, phase: np.ndarray, rate: float,
              live: int) -> np.ndarray:
    """(live, n) due time of every stream's j-th live sample."""
    return epoch + phase[None, :] + np.arange(live)[:, None] / rate


def window_stats(due: np.ndarray, deliv_t: np.ndarray, w0: float,
                 w1: float) -> dict:
    """The end-to-end numbers of a window [w0, w1): every sample due in
    it is attempted; its latency runs from its due time to its
    verdict's delivery, also where that came after the close (drained);
    one never delivered (NaN) counts as failed and has no latency."""
    in_win = (due >= w0) & (due < w1)
    got = ~np.isnan(deliv_t)
    lat = (deliv_t - due)[in_win & got]
    pct = (lambda q: float(np.percentile(lat, q) * 1e3) if lat.size
           else float("nan"))
    return {"attempted": int(in_win.sum()),
            "failed": int((in_win & ~got).sum()),
            "verdicts_per_s": float(((deliv_t >= w0) & (deliv_t < w1))
                                    .sum() / (w1 - w0)),
            "verdict_p50_ms": pct(50), "verdict_p95_ms": pct(95)}


def drive(ctx) -> dict:
    from repro.launch.batching import Request
    cfg, p = ctx.cfg, ctx.params
    n, hist, rate = int(cfg["streams"]), int(cfg["history"]), \
        float(cfg["rate_hz"])
    warm, seconds = float(p["warm_seconds"]), float(ctx.seconds)
    # room for the warm-up, the window, and a compile of up to
    # `drain_seconds` before the first live verdict
    live_cap = int(math.ceil((warm + seconds
                              + float(p["drain_seconds"])) * rate)) + 2
    x = ctx.signal(t_len=hist + live_cap, n=n)
    xs = np.ascontiguousarray(x.T)
    phase = _phases(p["phase"], n, rate, ctx.rng)
    order = np.argsort(phase, kind="stable")
    ph_sorted = phase[order]
    rids = [f"t{i}" for i in range(n)]
    index = {rid: i for i, rid in enumerate(rids)}
    store = ctx.reference.Store(hist + live_cap, n)
    delivered = np.zeros(n, np.int64)
    deliv_t = np.full((live_cap, n), np.nan)
    fed_t = np.full((live_cap, n), np.nan)
    work = ctx.work_by_tick

    def absorb(events):
        for t, ev in events:
            i = index[ev.rid]
            d = ev.data
            k = int(d["n"])
            pos = int(delivered[i])
            store.add(i, pos, d)
            delivered[i] = pos + k
            if pos + k > hist:
                deliv_t[max(pos - hist, 0):pos + k - hist, i] = t
            w = work.setdefault(d["dispatch_tick"], [0, 0])
            w[0] += k
            w[1] += 1

    gw = ctx.gateway
    sched = gw.sched
    for i in range(n):
        if not sched.submit(Request(rids[i], history=xs[i, :hist])):
            raise RuntimeError("admission queue refused a tenant")
    gw.step()   # admits every tenant and dispatches its history
    ctx.log(f"{n} tenants admitted")
    deadline = ctx.clock() + float(p["drain_seconds"])
    absorb(gw.take_events())
    while int(delivered.sum()) < n * hist:
        if ctx.clock() > deadline:
            raise RuntimeError("history replay did not finish")
        gw.step()
        absorb(gw.take_events())

    ctx.log("history replayed")
    clock = ctx.clock
    epoch = clock()

    def feed(q0, q1, t):
        for q in range(q0, q1):
            j, r = divmod(q, n)
            i = int(order[r])
            sched.feed(rids[i], xs[i, hist + j:hist + j + 1])
            fed_t[j, i] = t

    # the window opens `warm_seconds` after the first live verdict, so
    # the live program's compilation and the backlog it leaves fall
    # into set-up
    w0 = w1 = None
    q_fed, q_end = 0, n * (live_cap - 1)
    idle = False
    while True:
        now = clock()
        if w0 is None and delivered.sum() > n * hist:
            w0 = now + warm
            w1 = w0 + seconds
            ctx.window = (w0, w1)
            q_end = due_count(w1 - 1e-9, epoch, rate, ph_sorted)
            if q_end > n * (live_cap - 1):
                raise RuntimeError("live sample buffer too short")
        if w1 is not None and now >= w1:
            break
        if w0 is None and now > epoch + float(p["drain_seconds"]):
            raise RuntimeError("no live verdict came back")
        if w0 is not None:
            ctx.tick_hook(now)
        q_now = min(due_count(now, epoch, rate, ph_sorted), q_end)
        fresh = q_now > q_fed
        if fresh:
            with gw.spans.span("gen"):
                feed(q_fed, q_now, now)
            q_fed = q_now
        gw.step()
        events = gw.take_events()
        absorb(events)
        if not fresh and not events and idle:
            # nothing due and nothing in flight: wait for the next due
            nxt = epoch + (ph_sorted[q_fed % n] + q_fed // n) / rate
            wait = min(nxt - clock(), 1e-3)
            if wait > 0:
                ctx.sleep(wait)
        idle = not events
    ctx.tick_hook(None)
    ctx.log(f"window closed after {sched.tick_no} ticks")
    # samples created before the close that the generator had not yet
    # fed are fed now, late, and keep their due times
    feed(q_fed, q_end, clock())
    q_fed = q_end

    lengths = np.zeros(n, np.int64)
    np.add.at(lengths, order[np.arange(q_end) % n], 1)
    lengths += hist
    deadline = w1 + float(p["drain_seconds"])
    while (delivered < lengths).any() and clock() < deadline:
        gw.step()
        absorb(gw.take_events())

    ctx.log("drained")
    due = due_times(epoch, phase, rate, live_cap)
    stats = window_stats(due, deliv_t, w0, w1)
    return {
        "attempted": stats.pop("attempted"),
        "failed": stats.pop("failed"),
        "metrics": stats,
        "gen_lag_s": (fed_t - due)[ctx.in_stretch(fed_t)],
        "check": lambda ref, limits: ref.compare(
            store, lengths, ref.expected(x, cfg), limits),
        "control": lambda ref, limits: ref.compare(
            ref.control_store(store, x, cfg), lengths,
            ref.expected(x, cfg), limits),
    }

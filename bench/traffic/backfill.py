"""Closed-loop backfill: after a parent's restart every tenant
reconnects at once and re-sends its replicated gap, one request per
gap, from a cold state (no history), keeping a fixed number of samples
queued after every scheduler step so that every tick is a full frame.
Tenant c re-sends recording (c + p) mod R on its pass p; when a gap has
been sent in full its request is closed and the next opens at once.

Parameters (the workload file's "params"):
  recordings     gap recordings made from the seed
  gap            samples per recording
  backlog        samples kept queued per tenant (sent, not yet
                 delivered)
  warm_seconds   backfill after the first verdicts and before the
                 window opens (set-up)
  drain_seconds  how long past the window a verdict may still come
"""
from __future__ import annotations

import numpy as np

from bench.traffic.closed_loop import Client


class GapClient(Client):
    """The reconnecting tenants: `Client`'s replay, keeping every
    verdict's member bitmask, vote and score sums."""

    def absorb(self, events, work: dict) -> None:
        win = self.window
        for t, ev in events:
            key = self.key_of[ev.rid]
            d = ev.data
            k = int(d["n"])
            self.store.add(key, d)
            self.got_total[key[0]] += k
            if win is not None and win[0] <= t < win[1]:
                self.in_window += k
            w = work.setdefault(d["dispatch_tick"], [0, 0])
            w[0] += k
            w[1] += 1


def drive(ctx) -> dict:
    cfg, p = ctx.cfg, ctx.params
    n, gap = int(cfg["streams"]), int(p["gap"])
    recs = np.ascontiguousarray(
        ctx.signal(t_len=gap, n=int(p["recordings"])).T)
    gw = ctx.gateway
    sched = gw.sched
    ref = ctx.reference
    client = GapClient(sched, recs, n, int(p["backlog"]),
                       ref.GapStore(n, gap))
    clock = ctx.clock

    def step():
        gw.step()
        client.absorb(gw.take_events(), ctx.work_by_tick)
        with gw.spans.span("gen"):
            client.top_up()

    with gw.spans.span("gen"):
        client.top_up()
    # warm up for `warm_seconds` after the first verdicts come back (the
    # program's compilation falls before them)
    deadline = clock() + float(p["drain_seconds"])
    while not client.got_total.any():
        if clock() > deadline:
            raise RuntimeError("no verdict came back")
        step()
    ctx.log("first verdicts")
    t_warm = clock() + float(p["warm_seconds"])
    while clock() < t_warm:
        step()

    w0 = clock()
    ctx.log(f"window opens after {sched.tick_no} ticks")
    w1 = w0 + float(ctx.seconds)
    ctx.window = client.window = (w0, w1)
    fed_at_open = int(client.fed_total.sum())
    while True:
        now = clock()
        if now >= w1:
            break
        ctx.tick_hook(now)
        step()
    ctx.tick_hook(None)
    ctx.log(f"window closed after {sched.tick_no} ticks")
    attempted = int(client.fed_total.sum()) - fed_at_open
    deadline = w1 + float(p["drain_seconds"])
    while ((client.got_total < client.fed_total).any()
           and clock() < deadline):
        gw.step()
        client.absorb(gw.take_events(), ctx.work_by_tick)
    ctx.log("drained")
    fed, store = dict(client.fed), client.store
    upto = max(fed.values())
    return {
        "attempted": attempted,
        "failed": int(np.maximum(client.fed_total - client.got_total,
                                 0).sum()),
        "metrics": {"verdicts_per_s": client.in_window / float(ctx.seconds)},
        "check": lambda ref, limits: ref.compare_gaps(
            store, fed, ref.expected_gaps(recs, cfg, upto), limits),
        "control": lambda ref, limits: ref.compare_gaps(
            ref.control_gap_store(store, recs, cfg, upto), fed,
            ref.expected_gaps(recs, cfg, upto), limits),
    }

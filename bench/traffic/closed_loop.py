"""Closed-loop replay: a client replays recordings, one stream (one
request) per recording, and keeps each channel's backlog topped up to
a fixed number of samples after every scheduler step, so the next
frames are always queued.  When a recording has been sent in full its
request is closed and the next recording opens on that channel at
once; channel c replays recording (c + p) mod R on its pass p.

Parameters (the workload file's "params"):
  backlog        samples kept queued per channel (sent, not yet
                 delivered)
  warm_seconds   replay after the first verdicts and before the window
                 opens (set-up)
  drain_seconds  how long past the window a verdict may still come
"""
from __future__ import annotations

import numpy as np


class Client:
    """The replaying client: what it has sent and what has come back,
    per channel and per recording pass."""

    def __init__(self, sched, recs: np.ndarray, channels: int,
                 backlog: int, store):
        from repro.launch.batching import Request
        self._request = Request
        self.sched, self.recs, self.n = sched, recs, channels
        self.backlog, self.store = backlog, store
        self.n_rec, self.length = recs.shape
        self.key_of = {}                        # rid -> (channel, pass)
        self.fed = {}                           # (channel, pass) -> fed
        self.pass_no = np.zeros(channels, np.int64)
        self.in_pass = np.zeros(channels, np.int64)
        self.fed_total = np.zeros(channels, np.int64)
        self.got_total = np.zeros(channels, np.int64)
        self.window = None
        self.in_window = 0                      # verdicts delivered in it
        for c in range(channels):
            self._open(c)

    def stream_of(self, key) -> int:
        """The recording a pass replays."""
        c, ps = key
        return (c + ps) % self.n_rec

    @staticmethod
    def rid_of(c: int, ps: int) -> str:
        return f"c{c}p{ps}"

    def _open(self, c: int) -> None:
        key = (c, int(self.pass_no[c]))
        rid = self.rid_of(*key)
        self.key_of[rid] = key
        self.fed[key] = 0
        if not self.sched.submit(self._request(rid)):
            raise RuntimeError("admission queue refused a recording")

    def top_up(self) -> None:
        """Send each channel what it takes to hold `backlog` samples
        sent and not yet delivered, rolling over to the next recording
        (a new request) where one ends."""
        for c in range(self.n):
            need = self.backlog - int(self.fed_total[c]
                                      - self.got_total[c])
            while need > 0:
                key = (c, int(self.pass_no[c]))
                at = int(self.in_pass[c])
                take = min(need, self.length - at)
                rec = self.recs[self.stream_of(key)]
                self.sched.feed(self.rid_of(*key), rec[at:at + take])
                self.fed[key] += take
                self.in_pass[c] += take
                self.fed_total[c] += take
                need -= take
                if at + take == self.length:
                    self.sched.close(self.rid_of(*key))
                    self.pass_no[c] += 1
                    self.in_pass[c] = 0
                    self._open(c)

    def absorb(self, events, work: dict) -> None:
        """Take delivered verdicts: keep them for the check, count them,
        and note the work of the call that produced them."""
        win = self.window
        for t, ev in events:
            key = self.key_of[ev.rid]
            d = ev.data
            k = int(d["n"])
            self.store.add(key, self.store.count.get(key, 0), d["outlier"])
            self.got_total[key[0]] += k
            if win is not None and win[0] <= t < win[1]:
                self.in_window += k
            w = work.setdefault(d["dispatch_tick"], [0, 0])
            w[0] += k
            w[1] += 1


def drive(ctx) -> dict:
    cfg, p = ctx.cfg, ctx.params
    recs = ctx.signal(fs=float(cfg["rate_hz"]))
    gw = ctx.gateway
    sched = gw.sched
    client = Client(sched, recs, int(cfg["streams"]), int(p["backlog"]),
                    ctx.reference.Store())
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
    fed, store, lane = dict(client.fed), client.store, client.stream_of
    return {
        "attempted": attempted,
        "failed": int(np.maximum(client.fed_total - client.got_total,
                                 0).sum()),
        "metrics": {"verdicts_per_s": client.in_window / float(ctx.seconds)},
        "check": lambda ref, limits: ref.compare(
            store, fed, ref.expected(recs, cfg), lane, limits),
        "control": lambda ref, limits: ref.compare(
            ref.control_store(store, recs, cfg, lane), fed,
            ref.expected(recs, cfg), lane, limits),
    }

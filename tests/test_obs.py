"""Acceptance suite for `repro.obs` (ISSUE 6 — observability).

The registry's label semantics and histogram bucket arithmetic are
pinned directly; the weighted nearest-rank quantile is pinned against
the scheduler's *old* exact sort-based percentile computation on a
fixed workload whose observations land on bucket edges; the tracer's
ring buffer must survive wraparound in order and export schema-valid
Chrome trace JSON; and the event bus must stream verdicts in
retirement order, bit-exact (Q path) with what `results()` returns
after the fact.  The drain-flush regression test closes the loop: a
bare `drain()` (no intervening `results()`/`telemetry()` reads) must
leave nothing in flight and all telemetry complete.  A traced
scheduler nests one span per phase inside each `tick`, its transfer
byte counts agree with what it sends and fetches, tracing changes no
verdict, and a fresh jit shows as exactly one `compile` span.
"""
import json
import time

import jax
import numpy as np
import pytest

from repro.fixedpoint import QFormat
from repro.launch.batching import BatchingScheduler, Request
from repro.launch.serve import serve_streams
from repro.obs import (EventBus, LATENCY_MS_BUCKETS, MetricsRegistry,
                       NULL_TRACER, TickTracer, compile_watch,
                       get_registry)

FMT = QFormat(32, 20)


# ----------------------------------------------------------- registry
def test_counter_and_gauge_label_semantics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "req", ("sched",))
    c.labels(sched="a").inc()
    c.labels(sched="a").inc(2)
    c.labels(sched="b").inc(5)
    # same label value -> the same child; different value -> distinct
    assert c.labels(sched="a").value == 3
    assert c.labels(sched="b").value == 5
    with pytest.raises(ValueError):
        c.labels(wrong="a")         # label names must match the axes
    with pytest.raises(ValueError):
        c.labels(sched="a").inc(-1)  # counters only go up
    g = reg.gauge("depth")           # label-free: family-level methods
    g.set(4)
    g.dec()
    assert g.value == 3
    with pytest.raises(ValueError):
        c.inc()  # family has label axes: must go through .labels()


def test_registry_get_or_create_and_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("ticks_total", "t", ("sched",))
    assert reg.counter("ticks_total", "t", ("sched",)) is a
    with pytest.raises(ValueError):
        reg.gauge("ticks_total")                  # kind conflict
    with pytest.raises(ValueError):
        reg.counter("ticks_total", "t", ("pool",))  # label conflict
    h = reg.histogram("wall_ms", buckets=(1.0, 2.0))
    with pytest.raises(ValueError):
        reg.histogram("wall_ms", buckets=(1.0, 5.0))  # bucket conflict
    assert reg.histogram("wall_ms", buckets=(1.0, 2.0)) is h
    assert "wall_ms" in reg and reg.get("nope") is None


def test_histogram_bucket_edges_are_inclusive():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 2.0, 2.00001, 4.0, 99.0):
        h.observe(v)
    # le edges are inclusive (Prometheus): 1.0 lands in the 1.0 bucket
    assert dict((ub, c) for ub, c in h._default_child().buckets()) == {
        1.0: 2, 2.0: 3, 4.0: 5, float("inf"): 6}
    assert h.count == 6
    assert h.sum == pytest.approx(0.5 + 1.0 + 2.0 + 2.00001 + 4.0 + 99.0)
    with pytest.raises(ValueError):
        h.observe(1.0, weight=0)


def test_quantile_matches_old_exact_computation():
    """Regression (ISSUE 6 satellite): `stats()` percentiles moved from
    an O(n log n) re-sort of the call log to the O(1) running
    histogram.  On a fixed workload whose wall times land on bucket
    edges (the regime the bucket ladder is designed for), the
    histogram's weighted nearest-rank quantile must be *identical* to
    the old computation."""
    # (wall_s, retired) pairs exactly as the scheduler logged them;
    # wall_s * 1e3 lands on LATENCY_MS_BUCKETS edges, weights sum to 16
    calls = [(0.0001, 1), (0.001, 3), (0.0025, 4),
             (0.01, 6), (0.1, 2)]
    # the old BatchingScheduler.stats() body, verbatim
    walls = [c[0] for c in calls]
    weights = [max(c[1], 1) for c in calls]
    order = np.argsort(walls)
    w = np.asarray(weights, np.float64)[order]
    cum = np.cumsum(w) / w.sum()
    sw = np.asarray(walls)[order]

    def wpct(q):
        i = min(int(np.searchsorted(cum, q)), len(sw) - 1)
        return float(sw[i] * 1e3)

    reg = MetricsRegistry()
    h = reg.histogram("wall_ms", buckets=LATENCY_MS_BUCKETS)
    for wall, retired in calls:
        h.observe(wall * 1e3, weight=max(retired, 1))
    for q in (0.05, 0.25, 0.5, 0.75, 0.95, 1.0):
        assert h.quantile(q) == wpct(q), q


def test_prometheus_text_golden():
    reg = MetricsRegistry()
    reg.counter("sched_ticks_total", "scheduler ticks",
                ("sched",)).labels(sched="s0").inc(7)
    reg.gauge("pool_occupancy").set(3)
    h = reg.histogram("wall_ms", "wall", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(10.0, weight=2)
    assert reg.to_text() == """\
# TYPE pool_occupancy gauge
pool_occupancy 3
# HELP sched_ticks_total scheduler ticks
# TYPE sched_ticks_total counter
sched_ticks_total{sched="s0"} 7
# HELP wall_ms wall
# TYPE wall_ms histogram
wall_ms_bucket{le="1"} 1
wall_ms_bucket{le="10"} 3
wall_ms_bucket{le="+Inf"} 3
wall_ms_sum 20.5
wall_ms_count 3
"""


def test_snapshot_shape_is_json_ready():
    reg = MetricsRegistry()
    reg.counter("c", "", ("k",)).labels(k="x").inc()
    h = reg.histogram("h", buckets=(1.0,))
    h.observe(0.5)
    snap = reg.snapshot()
    json.dumps(snap)  # plain JSON, +Inf included (as the string "+Inf")
    assert snap["c"]["samples"] == [{"labels": {"k": "x"}, "value": 1.0}]
    hs = snap["h"]["samples"][0]
    assert (hs["count"], hs["p50"]) == (1.0, 1.0)
    assert hs["buckets"] == [[1.0, 1.0], ["+Inf", 1.0]]


# ------------------------------------------------------------- tracer
def test_tracer_ring_wraparound_keeps_order():
    tr = TickTracer(capacity=8)
    for i in range(20):
        tr.instant(f"ev{i}", i=i)
    assert len(tr) == 8
    assert tr.total == 20
    assert tr.dropped == 12
    names = [e["name"] for e in tr.events()]
    assert names == [f"ev{i}" for i in range(12, 20)]  # oldest first
    ts = [e["ts"] for e in tr.events()]
    assert ts == sorted(ts)


def test_chrome_trace_schema():
    tr = TickTracer(capacity=64)
    with tr.span("dispatch", device=True, tick=1, t=8):
        pass
    tr.instant("pool.resize", frm=4, to=8)
    doc = tr.to_chrome_trace()
    json.loads(json.dumps(doc))  # valid JSON end to end
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["otherData"] == {"recorded": 2, "dropped": 0}
    evs = doc["traceEvents"]
    assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
    span = next(e for e in evs if e["name"] == "dispatch")
    assert span["ph"] == "X" and span["dur"] >= 0
    assert {"pid", "tid", "ts"} <= set(span)
    assert span["args"] == {"tick": 1, "t": 8}
    inst = next(e for e in evs if e["name"] == "pool.resize")
    assert inst["ph"] == "i" and "dur" not in inst


def test_tracer_constructs_in_constant_time():
    """The ring grows as it fills, so even a 2^20-event tracer builds
    in well under a millisecond, and its clock origin is taken inside
    the constructor's interval (at its end)."""
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        tr = TickTracer(capacity=1 << 20)
        t1 = time.perf_counter()
        assert t0 <= tr.origin <= t1
        best = min(best, t1 - t0)
    assert best < 1e-3
    assert len(tr) == 0 and tr.events() == [] and tr.dropped == 0


def test_fresh_jit_records_one_compile_span():
    """JAX's backend-compile event becomes one `compile` span in every
    enabled tracer and one increment of the global counters; a second
    call of the same program compiles nothing."""
    tr = TickTracer(capacity=64)
    watch = compile_watch()
    reg = get_registry()
    n0, s0 = watch.compiles, watch.compile_s
    fam0 = reg.get("jax_compiles_total").value
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.ones((7, 13), np.float32)
    t0 = time.perf_counter()
    jax.block_until_ready(f(x))
    t1 = time.perf_counter()
    spans = [e for e in tr.events() if e["name"] == "compile"]
    assert len(spans) == 1
    assert watch.compiles == n0 + 1
    assert reg.get("jax_compiles_total").value == fam0 + 1
    sp = spans[0]
    assert sp["ph"] == "X" and "lambda" in sp["args"]["fun"]
    assert sp["dur"] * 1e-6 == pytest.approx(watch.compile_s - s0)
    start = tr.origin + sp["ts"] * 1e-6
    assert t0 <= start and start + sp["dur"] * 1e-6 <= t1
    jax.block_until_ready(f(x))
    assert len([e for e in tr.events() if e["name"] == "compile"]) == 1
    assert watch.compiles == n0 + 1


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("anything", tick=1):
        pass
    assert NULL_TRACER.instant("x") is None
    assert NULL_TRACER.events() == []
    assert NULL_TRACER.to_chrome_trace()["traceEvents"] == []


# ---------------------------------------------------------- event bus
def test_event_bus_pubsub_and_drop_oldest():
    bus = EventBus()
    assert not bus.active
    assert bus.publish("done", 0, "r0") is None  # silent path: no-op
    sub = bus.subscribe(maxlen=3)
    assert bus.active
    for i in range(5):
        bus.publish("admitted", i, f"r{i}", slot=i)
    evs = sub.poll()
    assert [e.rid for e in evs] == ["r2", "r3", "r4"]  # oldest dropped
    assert sub.dropped == 2
    assert [e.seq for e in evs] == sorted(e.seq for e in evs)
    assert evs[0].data == {"slot": 2}
    assert sub.poll() == []  # drained
    sub.close()
    bus.publish("done", 9, "rX")
    assert sub.poll() == [] and not bus.active


def test_event_bus_attach_callback_and_iter():
    bus = EventBus()
    seen = []
    cb = bus.attach(seen.append)
    with bus.subscribe() as sub:
        bus.publish("a", 1)
        bus.publish("b", 2)
        assert [e.kind for e in sub] == ["a", "b"]
    assert [e.kind for e in seen] == ["a", "b"]
    bus.detach(cb)
    bus.publish("c", 3)
    assert len(seen) == 2


# ----------------------------------------- scheduler/pool integration
def _run_workload(sched, specs, feed_steps=True):
    """Submit, trickle-feed, close and drain a {rid: (hist, live)} mix."""
    for rid, (h, live) in specs.items():
        assert sched.submit(Request(rid, h, m=2.5))
    fed = {rid: 0 for rid in specs}
    for _ in range(200):
        for rid, (h, live) in specs.items():
            take = min(1, len(live) - fed[rid])
            if take and rid in sched.stats_by_rid:
                sched.feed(rid, live[fed[rid]:fed[rid] + 1])
                fed[rid] += 1
            if fed[rid] == len(live) and rid not in sched._finished \
                    and rid in sched.runs and not sched.runs[rid].req.closed:
                sched.close(rid)
        sched.step()
        if sched.completed == len(specs):
            break
    else:
        raise AssertionError("workload did not drain")


def _specs(n=4, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for i in range(n):
        h = rng.normal(size=(int(rng.integers(4, 20)),)).astype(np.float32)
        lv = rng.normal(size=(int(rng.integers(1, 6)),)).astype(np.float32)
        lv[len(lv) // 2] += 12.0  # guarantee some flags
        out[f"r{i}"] = (h, lv)
    return out


def test_event_stream_matches_results_bit_exact():
    """The event-bus ordering contract (Q path): concatenating a
    request's `chunk_retired` outlier payloads in seq order reproduces
    `results()` bit-for-bit, and the streamed flag counts sum to the
    request's telemetry."""
    specs = _specs(4, seed=3)
    sched = BatchingScheduler("pallas-q", fmt=FMT, buckets=(2, 4),
                              chunk_t=4, collect=True)
    sub = sched.subscribe()
    _run_workload(sched, specs)
    evs = sub.poll()
    assert [e.seq for e in evs] == sorted(e.seq for e in evs)
    kinds = {e.kind for e in evs}
    assert {"admitted", "chunk_retired", "done"} <= kinds
    for rid in specs:
        chunks = [e for e in evs
                  if e.kind == "chunk_retired" and e.rid == rid]
        streamed = np.concatenate([e.data["outlier"] for e in chunks])
        res = sched.results(rid)
        np.testing.assert_array_equal(streamed, res["outlier"],
                                      err_msg=rid)
        np.testing.assert_array_equal(
            np.concatenate([e.data["ecc"] for e in chunks]),
            res["ecc"], err_msg=rid)
        st = sched.telemetry(rid)
        assert sum(e.data["flags"] for e in chunks) == st.flags
        assert sum(e.data["n"] for e in chunks) == st.samples
        done = next(e for e in evs if e.kind == "done" and e.rid == rid)
        assert done.data["samples"] == st.samples
        assert done.data["flags"] == st.flags
    # chunk_retired events stream at retirement: each request's first
    # chunk event precedes its done event in publish order
    for rid in specs:
        seqs = [e.seq for e in evs if e.rid == rid]
        done_seq = next(e.seq for e in evs
                        if e.kind == "done" and e.rid == rid)
        assert done_seq == max(seqs)


def test_trace_spans_reconcile_with_metrics():
    """dispatch spans == retire spans == the calls counter, and the
    dispatched sample total equals the samples-retired counter — the
    trace and the registry tell one story."""
    specs = _specs(3, seed=5)
    tr = TickTracer(capacity=4096)
    sched = BatchingScheduler("scan", fmt=FMT, buckets=(2, 4),
                              chunk_t=4, tracer=tr, measure_latency=True)
    _run_workload(sched, specs)
    evs = tr.events()
    dispatch = [e for e in evs if e["name"] == "dispatch"]
    retire = [e for e in evs if e["name"] == "retire"]
    calls = int(sched._c_calls.value)
    assert len(dispatch) == len(retire) == calls > 0
    assert (sum(e["args"]["samples"] for e in dispatch)
            == int(sched._c_samples.value)
            == sum(len(h) + len(lv) for h, lv in specs.values()))
    admits = [e for e in evs if e["name"] == "admit"]
    assert len(admits) == len(specs)
    # registry totals match the stats() view
    s = sched.stats()
    assert s["ticks"] == sched.tick_no
    assert s["completed"] == len(specs)
    assert s["chunk_latency"]["calls"] == len(sched.call_log)


PHASES = ("assemble", "dispatch", "retire", "account")


@pytest.mark.parametrize("shards", [1, 2], ids=["pool", "sharded"])
def test_tick_spans_nest_every_phase(shards):
    """One `tick` span per `step()`; every other span of the tick lies
    inside its tick on the same thread; each fused call has one span
    of each per-call phase; admission, acquisition and completion
    spans account for every request."""
    specs = _specs(5, seed=7)
    tr = TickTracer(capacity=1 << 14)
    sched = BatchingScheduler("scan", buckets=(2, 4), chunk_t=4,
                              shards=shards, tracer=tr)
    _run_workload(sched, specs)
    evs = [e for e in tr.events() if e["ph"] == "X"]
    ticks = [e for e in evs if e["name"] == "tick"]
    assert [e["args"]["tick"] for e in ticks] == list(
        range(1, sched.tick_no + 1))
    eps = 1e-3  # us: ts and dur are rounded separately
    for e in evs:
        # compiles are JAX's, wherever it compiles (construction too)
        if e["name"] in ("tick", "compile"):
            continue
        holder = [t for t in ticks if t["tid"] == e["tid"]
                  and t["ts"] - eps <= e["ts"]
                  and e["ts"] + e["dur"] <= t["ts"] + t["dur"] + eps]
        assert len(holder) == 1, e
        if "tick" in e.get("args", {}):
            assert e["args"]["tick"] == holder[0]["args"]["tick"]
    calls = int(sched._c_calls.value)
    for name in PHASES:
        assert len([e for e in evs if e["name"] == name]) == calls > 0
    admission = [e for e in evs if e["name"] == "admission"]
    assert sum(e["args"]["admitted"] for e in admission) == len(specs)
    acquire = [e for e in evs if e["name"] == "acquire"]
    refused = sum(ch.value for _, ch in
                  sched.registry.get("pool_full_total").series())
    assert len(acquire) == len(specs) + refused
    assert all(e["args"]["n"] == 1 and "resized" in e["args"]
               for e in acquire)
    assert any(e["args"]["resized"] for e in acquire)  # 2 -> 4 slots
    complete = [e for e in evs if e["name"] == "complete"]
    assert sum(e["args"]["completed"] for e in complete) == len(specs)


@pytest.mark.parametrize("backend,collect", [("scan", True),
                                             ("pallas-q", False)])
def test_transfer_bytes_match_what_moves(backend, collect):
    """`h2d_bytes` on the dispatch spans and `d2h_bytes` on the retire
    spans sum to the bytes handed to the pool and fetched from its
    outputs, and to the scheduler's counters."""
    specs = _specs(4, seed=9)
    tr = TickTracer(capacity=1 << 14)
    sched = BatchingScheduler(backend, fmt=FMT, buckets=(2, 4),
                              chunk_t=4, collect=collect, tracer=tr)
    sent, fetched = [0], [0]
    process = sched.pool.process

    def watched(x, active=None, valid_lens=None):
        out = process(x, active=active, valid_lens=valid_lens)
        sent[0] += x.nbytes + valid_lens.nbytes
        fetched[0] += out["outlier"].nbytes + (
            out["ecc"].nbytes if collect else 0)
        return out

    sched.pool.process = watched
    _run_workload(sched, specs)
    evs = tr.events()
    h2d = sum(e["args"]["h2d_bytes"] for e in evs
              if e["name"] == "dispatch")
    d2h = sum(e["args"]["d2h_bytes"] for e in evs
              if e["name"] == "retire")
    s = sched.stats()
    assert h2d == sent[0] == s["h2d_bytes"] > 0
    assert d2h == fetched[0] == s["d2h_bytes"] > 0
    assert s["h2d_bytes"] == int(sched.registry.get(
        "sched_h2d_bytes_total").labels(sched=sched.name).value)


def test_tracing_changes_no_verdict():
    """Verdicts (Q path, bit-exact) and flag counts are identical with
    the disabled `NULL_TRACER` and with a recording tracer."""
    specs = _specs(4, seed=13)
    runs = []
    for tracer in (NULL_TRACER, TickTracer(capacity=1 << 14)):
        sched = BatchingScheduler("pallas-q", fmt=FMT, buckets=(2, 4),
                                  chunk_t=4, collect=True, tracer=tracer)
        _run_workload(sched, specs)
        runs.append({rid: sched.results(rid) for rid in specs})
    for rid in specs:
        for key in ("ecc", "outlier"):
            np.testing.assert_array_equal(runs[0][rid][key],
                                          runs[1][rid][key],
                                          err_msg=f"{rid} {key}")


def test_drain_flushes_everything_without_reads():
    """Regression (ISSUE 6 satellite): a bare `drain()` — no
    `results()`/`telemetry()` reads forcing syncs first — must leave
    zero in-flight calls and complete telemetry: every sample
    accounted in the per-request stats, the call log, and the
    registry."""
    specs = _specs(4, seed=11)
    sched = BatchingScheduler("scan", fmt=FMT, buckets=(2, 4),
                              chunk_t=4, measure_latency=False)
    for rid, (h, lv) in specs.items():
        assert sched.submit(
            Request(rid, np.concatenate([h, lv]), m=2.5, closed=True))
    sched.drain()
    assert not sched._inflight
    assert sched.stats()["inflight_calls"] == 0
    assert int(sched._g_inflight.value) == 0
    total = sum(len(h) + len(lv) for h, lv in specs.values())
    assert int(sched._c_samples.value) == total
    assert sum(c["retired"] for c in sched.call_log) == total
    for rid, (h, lv) in specs.items():
        st = sched.stats_by_rid[rid]
        assert st.samples == len(h) + len(lv)
        assert st.done_tick is not None
        assert sum(n for _, n in st.chunk_latency_s) == st.samples
    # flags fetched by the final flush are accounted, not lost
    assert int(sched._c_flags.value) == sum(
        sched.stats_by_rid[rid].flags for rid in specs)


def test_scheduler_stats_reads_registry():
    """Counters behind tick_no/completed/rejected/short_ticks are
    registry instruments; two schedulers with private registries never
    mix values, and an injected shared registry keeps them apart by
    the instance label."""
    shared = MetricsRegistry()
    a = BatchingScheduler("scan", fmt=FMT, buckets=(2,), chunk_t=4,
                          registry=shared, name="A")
    b = BatchingScheduler("scan", fmt=FMT, buckets=(2,), chunk_t=4,
                          registry=shared, name="B")
    a.submit(Request("r0", np.zeros(6, np.float32), closed=True))
    a.drain()
    assert (a.completed, b.completed) == (1, 0)
    fam = shared.get("sched_completed_total")
    assert fam.labels(sched="A").value == 1
    assert fam.labels(sched="B").value == 0
    text = shared.to_text()
    assert 'sched_completed_total{sched="A"} 1' in text
    # pool + engine series share the registry, prefixed by owner name
    assert 'pool_occupancy{pool="A/pool"} 0' in text
    assert get_registry() is get_registry()  # process-global singleton


def test_serve_streams_on_event_and_metrics():
    rng = np.random.default_rng(2)
    streams = [(f"t{i}", rng.normal(size=10).astype(np.float32),
                rng.normal(size=3).astype(np.float32), 2.5)
               for i in range(3)]
    seen = []
    res = serve_streams(streams, backend="scan", buckets=(2, 4),
                        chunk_t=4, queue_limit=4,
                        on_event=seen.append)
    assert res["requests"] == 3
    done = [e for e in seen if e.kind == "done"]
    assert sorted(e.rid for e in done) == ["t0", "t1", "t2"]
    assert [e.seq for e in seen] == sorted(e.seq for e in seen)
    snap = res["metrics"]
    comp = snap["sched_completed_total"]["samples"][0]
    assert comp["value"] == 3.0
    assert "sched_call_wall_ms" in snap
    assert snap["sched_call_wall_ms"]["samples"][0]["count"] > 0
    json.dumps(snap)

"""Continuous-batching scheduler + autoscaling pool acceptance suite.

Acceptance (ISSUE 3 + ISSUE 4): interleaved prefill/decode across N
concurrent requests through `BatchingScheduler` must be bit-exact (Q
path) with each request run alone on a fresh engine; every tick is ONE
fused ragged (chunk_t, C) call, so a prefill tail retires in
ceil(history / chunk_t) ticks rather than draining 1/tick; the
`SlotPool` must grow and shrink through its bucket ladder without
perturbing live tenants; a full pool and a full admission queue must
be explicit backpressure, never silent drops; and the scheduler's
retention caps (`keep_finished`, `call_log_len`) must actually evict.
"""
import time
from dataclasses import asdict

import numpy as np
import pytest

from conftest import given_or_cases

from repro.engine import PoolFull, SlotPool, StreamEngine, list_backends
from repro.fixedpoint import QFormat
from repro.launch.batching import (BatchingScheduler, EvictedRequest,
                                   Request)

FMT = QFormat(32, 20)


def _mk_sched(backend, **kw):
    kw.setdefault("buckets", (2, 4))
    kw.setdefault("chunk_t", 8)
    return BatchingScheduler(backend, fmt=FMT, **kw)


def _workload(n, seed):
    """n requests: ragged history/live lengths, a burst, mixed m."""
    rng = np.random.default_rng(seed)
    specs = {}
    for i in range(n):
        h = rng.normal(size=(int(rng.integers(0, 30)),)).astype(np.float32)
        live = rng.normal(size=(int(rng.integers(0, 10)),)).astype(
            np.float32)
        if live.size and i % 3 == 0:
            live[live.size // 2] += 25.0
        specs[f"r{i}"] = (h, live, [1.5, 3.0, 6.0][i % 3])
    return specs


def _serve_interleaved(sched, specs, max_ticks=500):
    """Staggered submits (one per tick), live fed one sample per tick."""
    order = list(specs)
    fed = {rid: 0 for rid in specs}
    closed = set()
    for tick in range(max_ticks):
        if tick < len(order):
            rid = order[tick]
            h, live, m = specs[rid]
            assert sched.submit(Request(rid, h, m=m))
            if not live.size:
                sched.close(rid)
                closed.add(rid)
        for rid, (h, live, m) in specs.items():
            if rid not in sched.stats_by_rid or rid in closed:
                continue
            if fed[rid] < live.size:
                sched.feed(rid, live[fed[rid]:fed[rid] + 1])
                fed[rid] += 1
            if fed[rid] == live.size:
                sched.close(rid)
                closed.add(rid)
        sched.step()
        if sched.completed == len(specs):
            return
    raise AssertionError(f"did not drain: {sched.stats()}")


# ------------------------------------------- interleaved == isolated
@pytest.mark.parametrize("backend", list_backends())
@given_or_cases(
    "n,seed", [(5, 0), (4, 1), (6, 2)],
    lambda st: dict(n=st.integers(2, 6), seed=st.integers(0, 2 ** 16)),
    max_examples=3)
def test_interleaved_equals_isolated(backend, n, seed):
    specs = _workload(n, seed)
    sched = _mk_sched(backend, measure_latency=True)
    _serve_interleaved(sched, specs)

    for rid, (h, live, m) in specs.items():
        full = np.concatenate([h, live])
        res = sched.results(rid)
        assert res["outlier"].shape[0] == full.size
        if not full.size:
            continue
        # the oracle: this request alone on a fresh single-slot engine
        oracle = StreamEngine(1, backend, fmt=FMT, block_t=8, m=m)
        ref = oracle.process(full[:, None])
        np.testing.assert_array_equal(
            res["outlier"], np.asarray(ref["outlier"])[:, 0], err_msg=rid)
        if backend == "pallas-q":  # quantized datapath: exact bits
            np.testing.assert_array_equal(
                res["ecc"], np.asarray(ref["ecc"])[:, 0], err_msg=rid)
        else:
            np.testing.assert_allclose(
                res["ecc"], np.asarray(ref["ecc"])[:, 0],
                rtol=1e-4, atol=1e-6, err_msg=rid)
        st = sched.telemetry(rid)
        assert st.samples == full.size
        assert st.done_tick is not None


def test_prefill_tail_retires_in_ceil_ticks():
    """Regression (ISSUE 4): a 30-sample history on chunk_t=8 retires in
    ceil(30/8) = 4 fused calls — the 6-sample tail rides the same
    (chunk_t, C) program as the full chunks via its per-slot valid
    length, instead of draining 1 sample/tick on a trickle program."""
    sched = _mk_sched("scan", chunk_t=8)
    h = np.random.default_rng(0).normal(size=(30,)).astype(np.float32)
    sched.submit(Request("a", h))
    sched.close("a")
    ticks = sched.drain()
    assert ticks == 4                      # not 3 + 6 = 9 as before
    st = sched.telemetry("a")
    assert st.samples == 30
    assert st.prefill_chunks == 4          # 8 + 8 + 8 + 6
    assert st.decode_steps == 0            # no 1-sample drain ticks
    assert {c["kind"] for c in sched.call_log} == {"fused"}
    assert all(c["t"] == 8 for c in sched.call_log)  # one program shape
    assert [c["retired"] for c in sched.call_log] == [8, 8, 8, 6]


def test_mixed_prefill_decode_slots_share_one_call():
    """A prefill-heavy and a decode-phase request advance in the SAME
    fused call, each retiring its own sample count."""
    sched = _mk_sched("scan", chunk_t=8)
    h = np.random.default_rng(1).normal(size=(20,)).astype(np.float32)
    sched.submit(Request("big", h))        # prefill-heavy
    sched.submit(Request("drip"))          # decode-phase, fed 1/tick
    sched.close("big")
    for i in range(3):
        sched.feed("drip", [float(i)])
        sched.step()
    # each tick made exactly one fused call serving both slots
    log = list(sched.call_log)
    assert [c["kind"] for c in log] == ["fused"] * 3
    assert [c["slots"] for c in log] == [2, 2, 2]
    assert [c["retired"] for c in log] == [9, 9, 5]  # 8+1, 8+1, 4+1
    big, drip = sched.telemetry("big"), sched.telemetry("drip")
    assert big.samples == 20 and big.prefill_chunks == 3
    assert drip.samples == 3 and drip.decode_steps == 3


def test_backpressure_queue_and_pool():
    """Full admission queue rejects; full pool queues; both explicit."""
    sched = _mk_sched("scan", buckets=(2,), queue_limit=2)
    h = np.zeros((4,), np.float32)
    for i in range(4):
        ok = sched.submit(Request(f"r{i}", h))
        assert ok == (i < 2)               # queue_limit=2: r2, r3 rejected
    assert sched.rejected == 2
    sched.step()                           # admits r0, r1 (bucket 2)
    assert sched.submit(Request("r4", h))  # queue drained by admission
    assert sched.submit(Request("r5", h))
    sched.step()
    assert len(sched.runs) == 2            # pool full: r4/r5 wait queued
    assert len(sched.queue) == 2
    for rid in ("r0", "r1", "r4", "r5"):
        sched.close(rid)
    sched.drain()
    assert sched.completed == 4            # everyone served eventually


def test_results_and_feed_lifecycle_errors():
    sched = _mk_sched("scan")
    with pytest.raises(KeyError):
        sched.results("ghost")
    with pytest.raises(KeyError):
        sched.feed("ghost", [0.0])
    sched.submit(Request("a", np.zeros((3,), np.float32)))
    with pytest.raises(ValueError):
        sched.submit(Request("a"))         # duplicate rid
    sched.close("a")
    with pytest.raises(ValueError):
        sched.feed("a", [1.0])             # closed


# ------------------------------------------------ async loop (ISSUE 5)
def test_async_equals_sync_bit_exact():
    """Acceptance (ISSUE 5): the async double-buffered loop is
    bit-exact with the synchronous loop on the Q path — per-request
    ecc/outlier identical across an interleaved priority mix, because
    scheduling decisions depend only on host-side counters, never on
    fetched verdicts."""
    specs = _workload(5, seed=7)
    prios = {rid: ("latency" if i % 2 else "bulk")
             for i, rid in enumerate(specs)}

    def run(measure_latency):
        sched = _mk_sched("pallas-q", measure_latency=measure_latency,
                          class_weights={"latency": 3.0, "bulk": 1.0})
        order = list(specs)
        fed = {rid: 0 for rid in specs}
        closed = set()
        for tick in range(500):
            if tick < len(order):
                rid = order[tick]
                h, live, m = specs[rid]
                assert sched.submit(
                    Request(rid, h, m=m, priority=prios[rid]))
                if not live.size:
                    sched.close(rid)
                    closed.add(rid)
            for rid, (h, live, m) in specs.items():
                if rid not in sched.stats_by_rid or rid in closed:
                    continue
                if fed[rid] < live.size:
                    sched.feed(rid, live[fed[rid]:fed[rid] + 1])
                    fed[rid] += 1
                if fed[rid] == live.size:
                    sched.close(rid)
                    closed.add(rid)
            sched.step()
            if sched.completed == len(specs):
                return sched
        raise AssertionError("did not drain")

    sync, asyn = run(True), run(False)
    for rid in specs:
        rs, ra = sync.results(rid), asyn.results(rid)
        np.testing.assert_array_equal(rs["ecc"], ra["ecc"], err_msg=rid)
        np.testing.assert_array_equal(rs["outlier"], ra["outlier"],
                                      err_msg=rid)
        ts, ta = sync.telemetry(rid), asyn.telemetry(rid)
        assert (ts.samples, ts.flags) == (ta.samples, ta.flags)


def test_adaptive_decode_short_program():
    """Decode-only ticks ride the cached (decode_t, C) program instead
    of the full (chunk_t, C) chunk, and the program cache stays flat
    after warmup (no per-tick recompiles)."""
    sched = _mk_sched("scan", chunk_t=8, decode_t=1)
    h = np.random.default_rng(3).normal(size=(10,)).astype(np.float32)
    sched.submit(Request("a", h))
    sched.step()                           # prefill: avail 10 -> chunk
    sched.step()                           # tail 2 > decode_t -> chunk
    for i in range(5):                     # decode trickle: avail 1
        sched.feed("a", [float(i)])
        sched.step()
    sched.close("a")
    sched.drain()
    log = list(sched.call_log)
    assert [c["t"] for c in log] == [8, 8, 1, 1, 1, 1, 1]
    assert [c["retired"] for c in log] == [8, 2, 1, 1, 1, 1, 1]
    assert sched.short_ticks == 5
    st = sched.stats()
    # two cached programs at bucket 2, nothing else ever compiled
    assert st["programs"] == [(2, 1), (2, 8)]
    assert sched.telemetry("a").samples == 15


def test_drain_open_request_raises_helpfully():
    """Regression (ISSUE 5): drain with an open request must raise
    immediately, naming the rids, not spin max_ticks times."""
    sched = _mk_sched("scan")
    h = np.zeros((6,), np.float32)
    sched.submit(Request("open-a", h))
    with pytest.raises(RuntimeError, match=r"open-a.*close\(\)"):
        sched.drain()
    assert sched.tick_no < 10              # stalled detection, not 100k


def test_admission_during_pool_resize_tick():
    """A request admitted in a tick where the pool grows a bucket —
    while the previous tick's call is still in flight — is served
    bit-exactly (Q path): the in-flight outputs keep their dispatch-
    time slot indices and the re-padded state is exact."""
    rng = np.random.default_rng(9)
    hs = {f"r{i}": rng.normal(size=(12,)).astype(np.float32)
          for i in range(3)}
    sched = _mk_sched("pallas-q", buckets=(2, 4), chunk_t=4)
    sched.submit(Request("r0", hs["r0"]))
    sched.submit(Request("r1", hs["r1"]))
    sched.step()                           # bucket 2, call in flight
    sched.submit(Request("r2", hs["r2"]))
    sched.step()                           # grows 2 -> 4 mid-tick
    assert sched.pool.stats()["resizes"] == 1
    for rid in hs:
        sched.close(rid)
    sched.drain()
    for rid, h in hs.items():
        oracle = StreamEngine(1, "pallas-q", fmt=FMT, block_t=8)
        ref = oracle.process(h[:, None])
        res = sched.results(rid)
        np.testing.assert_array_equal(
            res["ecc"], np.asarray(ref["ecc"])[:, 0], err_msg=rid)
        np.testing.assert_array_equal(
            res["outlier"], np.asarray(ref["outlier"])[:, 0],
            err_msg=rid)


# ------------------------------------- priority admission (ISSUE 5)
def test_priority_weighted_admission_no_starvation():
    """A burst of bulk prefills cannot starve the latency class: the
    weighted-deficit queues admit latency tenants ahead of the bulk
    backlog."""
    sched = _mk_sched("scan", buckets=(2,), queue_limit=16,
                      class_weights={"bulk": 1.0, "latency": 3.0})
    h = np.zeros((4,), np.float32)
    for i in range(6):
        assert sched.submit(Request(f"b{i}", h, priority="bulk"))
        sched.close(f"b{i}")
    for i in range(2):
        assert sched.submit(Request(f"l{i}", h, priority="latency"))
        sched.close(f"l{i}")
    sched.drain()
    adm = {rid: sched.telemetry(rid).admitted_tick
           for rid in list(sched.stats_by_rid)}
    # latency submitted last but admitted within the first two ticks;
    # the bulk backlog tail waits behind them
    assert max(adm["l0"], adm["l1"]) <= 2
    assert max(adm[f"b{i}"] for i in range(6)) > 2
    classes = sched.stats()["classes"]
    assert classes["latency"]["completed"] == 2
    assert classes["bulk"]["completed"] == 6
    assert (classes["latency"]["queue_wait_ticks_p95"]
            <= classes["bulk"]["queue_wait_ticks_p95"])


def test_per_class_state_is_pruned_when_drained():
    """A forever-running gateway must not accumulate per-class state
    for every priority string ever seen: drained classes are pruned
    (ctor-declared weights are the one retained configuration)."""
    sched = _mk_sched("scan", class_weights={"latency": 2.0})
    for i in range(8):
        sched.submit(Request(f"r{i}", np.zeros((2,), np.float32),
                             priority=f"tenant-{i}"))  # unique classes
        sched.close(f"r{i}")
    sched.drain()
    assert sched.completed == 8
    assert not sched._queues and not sched._deficit
    assert set(sched._weights) == {"latency"}   # ctor config retained


def test_evicted_ring_survives_resubmit_cycle():
    """A rid that is evicted twice (resubmit cycle) must still report
    EvictedRequest after its *stale* ring entry rotates out — the ring
    is refcounted, not a set."""
    from collections import deque as _deque
    sched = _mk_sched("scan", keep_finished=1)
    sched._evicted = _deque(maxlen=2)           # tiny ring for rotation
    sched._note_evicted("a")                    # first eviction
    sched._note_evicted("a")                    # evicted again (reuse)
    sched._note_evicted("b")                    # rotates the stale "a"
    assert list(sched._evicted) == ["a", "b"]
    with pytest.raises(EvictedRequest):         # newer "a" entry lives
        sched.results("a")
    sched._note_evicted("c")                    # rotates the live "a"
    with pytest.raises(KeyError) as ei:
        sched.results("a")                      # now genuinely unknown
    assert not isinstance(ei.value, EvictedRequest)


# -------------------------------------- lifecycle telemetry (ISSUE 5)
def test_phase_transitions_prefill_to_decode():
    """Regression (ISSUE 5): `phase` must leave PREFILL once the
    history cursor passes the replayed prefix (it used to stay PREFILL
    for the whole decode phase)."""
    sched = _mk_sched("scan", chunk_t=8)
    h = np.zeros((10,), np.float32)
    sched.submit(Request("a", h))
    assert sched.request_phase("a") == "queued"
    sched.step()                           # consumed 8 < 10
    assert sched.request_phase("a") == "prefill"
    sched.step()                           # consumed 10 >= 10
    assert sched.request_phase("a") == "decode"
    sched.feed("a", [1.0])
    sched.step()
    assert sched.request_phase("a") == "decode"
    sched.close("a")
    sched.drain()
    assert sched.request_phase("a") == "done"
    with pytest.raises(KeyError):
        sched.request_phase("ghost")


def test_empty_history_starts_in_decode():
    sched = _mk_sched("scan")
    sched.submit(Request("d"))
    sched.step()
    assert sched.request_phase("d") == "decode"
    sched.close("d")
    sched.drain()


def test_evicted_rid_error_is_distinct():
    """Regression (ISSUE 5): results()/telemetry() on a request evicted
    by the keep_finished cap must raise a distinct error, not the same
    bare KeyError as a never-submitted rid."""
    sched = _mk_sched("scan", keep_finished=2)
    for i in range(5):
        sched.submit(Request(f"r{i}", np.zeros((2,), np.float32)))
        sched.close(f"r{i}")
    sched.drain()
    for fn in (sched.results, sched.telemetry, sched.request_phase):
        with pytest.raises(EvictedRequest, match="keep_finished=2"):
            fn("r0")
        with pytest.raises(KeyError) as ei:
            fn("never-submitted")
        assert not isinstance(ei.value, EvictedRequest)
        assert "unknown" in str(ei.value)
    assert isinstance(EvictedRequest("x"), KeyError)  # except-compat
    sched.results("r4")                    # retained rids still resolve


def test_latency_log_pairs_and_cap():
    """Regression (ISSUE 5): the per-request latency log records
    (wall, retired_this_call) pairs — the shared fused-call wall is no
    longer attributed wholesale to every member — and its cap is the
    `latency_log_len` ctor knob, not a hard-coded 4096."""
    sched = _mk_sched("scan", chunk_t=4, latency_log_len=3,
                      measure_latency=True)
    sched.submit(Request("a", np.zeros((18,), np.float32)))
    sched.close("a")
    sched.drain()                          # 5 calls: 4,4,4,4,2
    st = sched.telemetry("a")
    assert st.samples == 18
    assert len(st.chunk_latency_s) == 3    # capped by the ctor knob
    for wall, retired in st.chunk_latency_s:
        assert wall > 0 and retired == 4   # honest per-call weights


def test_feed_after_close_on_queued_request():
    """Edge (ISSUE 5): a request closed while still *queued* (pool
    full, never admitted) must reject feed the same way a running
    closed request does."""
    sched = _mk_sched("scan", buckets=(2,), queue_limit=4)
    for i in range(2):                     # occupy the whole pool
        sched.submit(Request(f"hold{i}", np.zeros((2,), np.float32)))
    sched.step()
    sched.submit(Request("q", np.zeros((2,), np.float32)))
    sched.step()                           # pool full: "q" stays queued
    assert sched.request_phase("q") == "queued"
    sched.close("q")
    with pytest.raises(ValueError, match="closed"):
        sched.feed("q", [1.0])
    for i in range(2):
        sched.close(f"hold{i}")
    sched.drain()
    assert sched.completed == 3            # q admitted after a release


# --------------------------------------------------- autoscaling pool
@pytest.mark.parametrize("backend", ["scan", "pallas-q"])
def test_pool_grow_preserves_tenants(backend):
    """Growing to the next bucket re-pads state without perturbing it."""
    rng = np.random.default_rng(1)
    xa = rng.normal(size=(20, 2)).astype(np.float32)
    xb = rng.normal(size=(20, 4)).astype(np.float32)
    xb[:, :2] = xa

    pool = SlotPool(backend, buckets=(2, 4), fmt=FMT, block_t=8)
    pool.acquire(2)
    assert pool.capacity == 2
    pool.process(xa)
    pool.acquire(1)                        # 3 tenants: bucket 2 -> 4
    assert pool.capacity == 4 and pool.resizes == 1
    out = pool.process(xb, active=[0, 1])

    flat = StreamEngine(2, backend, fmt=FMT, block_t=8)  # no-resize oracle
    flat.process(xa)
    ref = flat.process(xb[:, :2])
    np.testing.assert_array_equal(np.asarray(out["outlier"])[:, :2],
                                  np.asarray(ref["outlier"]))
    if backend == "pallas-q":
        np.testing.assert_array_equal(np.asarray(out["ecc"])[:, :2],
                                      np.asarray(ref["ecc"]))
    assert pool.engine.samples_seen[:3].tolist() == [40, 40, 0]


def test_pool_shrinks_and_caches_buckets():
    pool = SlotPool("scan", buckets=(2, 4, 8))
    slots = pool.acquire(7)
    assert pool.capacity == 8
    pool.release(slots[2:])                # max live index is 1 -> bucket 2
    assert pool.capacity == 2
    assert pool.stats()["compiled_buckets"] == [2, 8]
    pool.acquire(2)                        # back up a bucket
    assert pool.capacity == 4 and pool.occupancy == 4
    assert pool.stats()["compiled_buckets"] == [2, 4, 8]


def test_pool_full_is_explicit():
    pool = SlotPool("scan", buckets=(2, 4))
    pool.acquire(4)
    with pytest.raises(PoolFull) as ei:
        pool.acquire(1)
    assert ei.value.occupancy == 4 and ei.value.capacity == 4
    assert "4/4" in str(ei.value)


def test_finished_retention_is_bounded():
    """A forever-running gateway evicts its oldest finished requests."""
    sched = _mk_sched("scan", keep_finished=3)
    for i in range(6):
        sched.submit(Request(f"r{i}", np.zeros((2,), np.float32)))
        sched.close(f"r{i}")
    sched.drain()
    assert sched.completed == 6
    assert len(sched._finished) == 3
    sched.results("r5")                    # recent results retained
    with pytest.raises(KeyError):
        sched.results("r0")                # oldest evicted
    sched.submit(Request("r0"))            # ...and its rid is reusable
    assert sched.telemetry("r5").done_tick is not None
    # telemetry of evicted requests is gone too (no unbounded dict)
    assert set(sched.stats_by_rid) == {"r3", "r4", "r5", "r0"}


def test_call_log_retention_is_bounded():
    """Regression (ISSUE 4): the engine-call log must be a ring buffer —
    a long-lived gateway keeps only the newest `call_log_len` calls."""
    sched = _mk_sched("scan", chunk_t=2, call_log_len=5)
    sched.submit(Request("a", np.zeros((40,), np.float32)))
    sched.close("a")
    ticks = sched.drain()
    assert ticks == 20                     # 40 samples / chunk_t=2
    assert len(sched.call_log) == 5        # ring buffer, not 20 entries
    assert all(c["kind"] == "fused" for c in sched.call_log)
    # stats() keeps working on the bounded window
    assert sched.stats()["chunk_latency"]["calls"] == 5


def test_serve_streams_outlives_retention_cap():
    """Regression: serve_streams must read every request's telemetry
    after the drain even when the stream count exceeds the scheduler's
    default retention (it sizes keep_finished to the run)."""
    from repro.launch.serve import serve_streams
    streams = [(f"s{i}", np.zeros((3,), np.float32),
                np.zeros((0,), np.float32), None) for i in range(12)]
    res = serve_streams(streams, backend="scan", buckets=(2, 4),
                        chunk_t=2, keep_finished=4)
    assert res["requests"] == 12 and res["samples"] == 36
    assert len(res["per_request"]) == 12


def test_pool_per_tenant_m_survives_resize():
    pool = SlotPool("scan", buckets=(2, 4), m=3.0)
    pool.acquire(2, m=1.25)
    pool.acquire(1, m=9.0)                 # grows to bucket 4
    assert pool.engine.slot_m.tolist() == [1.25, 1.25, 9.0, 3.0]


# ------------------------------------------- deep pipeline (pipeline_depth)
def _run_depth(depth, specs, prios=None, backend="pallas-q",
               check_fence=False, **kw):
    """Serve `specs` interleaved at a given pipeline depth; optionally
    assert the fencing invariant (a slot in at most one in-flight call)
    after every tick."""
    sched = _mk_sched(backend, pipeline_depth=depth, **kw)
    order = list(specs)
    fed = {rid: 0 for rid in specs}
    closed = set()
    for tick in range(800):
        if tick < len(order):
            rid = order[tick]
            h, live, m = specs[rid]
            prio = (prios or {}).get(rid, "default")
            assert sched.submit(Request(rid, h, m=m, priority=prio))
            if not live.size:
                sched.close(rid)
                closed.add(rid)
        for rid, (h, live, m) in specs.items():
            if rid not in sched.stats_by_rid or rid in closed:
                continue
            if fed[rid] < live.size:
                sched.feed(rid, live[fed[rid]:fed[rid] + 1])
                fed[rid] += 1
            if fed[rid] == live.size:
                sched.close(rid)
                closed.add(rid)
        sched.step()
        if check_fence:
            slots = [s for inf in sched._inflight
                     for s in inf.slots.tolist()]
            assert len(slots) == len(set(slots)), \
                f"slot fenced twice in flight at tick {tick}: {slots}"
            assert len(sched._inflight) <= depth + 1
        if sched.completed == len(specs):
            return sched
    raise AssertionError("did not drain")


@pytest.mark.parametrize("depth", [2, 4])
def test_pipeline_depth_bit_exact_with_depth_1(depth):
    """Acceptance (ISSUE 7): depth-2/4 pipelines are bit-exact with
    depth 1 at the gateway level on the Q path — fencing keeps each
    slot's chunks in dispatch order, and chunk-exactness makes the
    per-request sample stream independent of tick partitioning."""
    specs = _workload(6, seed=23)
    prios = {rid: ("latency" if i % 2 else "bulk")
             for i, rid in enumerate(specs)}
    base = _run_depth(1, specs, prios,
                      class_weights={"latency": 3.0, "bulk": 1.0})
    deep = _run_depth(depth, specs, prios, check_fence=True,
                      class_weights={"latency": 3.0, "bulk": 1.0})
    for rid in specs:
        rb, rd = base.results(rid), deep.results(rid)
        np.testing.assert_array_equal(rb["ecc"], rd["ecc"], err_msg=rid)
        np.testing.assert_array_equal(rb["outlier"], rd["outlier"],
                                      err_msg=rid)
        tb, td = base.telemetry(rid), deep.telemetry(rid)
        assert (tb.samples, tb.flags) == (td.samples, td.flags)


def test_pipeline_fencing_under_slot_churn():
    """Attach/detach churn: completed requests release slots that new
    requests immediately recycle while older calls may still be in
    flight.  The fencing invariant must hold every tick and results
    must stay bit-exact with the depth-1 loop."""
    rng = np.random.default_rng(31)
    specs = {}
    for i in range(10):  # > 2x pool capacity: constant recycling
        h = rng.normal(size=(int(rng.integers(1, 12)),)).astype(
            np.float32)
        live = rng.normal(size=(int(rng.integers(0, 4)),)).astype(
            np.float32)
        specs[f"c{i}"] = (h, live, 3.0)
    base = _run_depth(1, specs)
    deep = _run_depth(4, specs, check_fence=True)
    for rid in specs:
        np.testing.assert_array_equal(base.results(rid)["outlier"],
                                      deep.results(rid)["outlier"],
                                      err_msg=rid)
        np.testing.assert_array_equal(base.results(rid)["ecc"],
                                      deep.results(rid)["ecc"],
                                      err_msg=rid)


def test_pipeline_programs_flat_after_warmup():
    """Depth > 1 must not defeat the program cache: after the first
    full+short programs compile, further ticks add no new (capacity, t)
    entries."""
    sched = _mk_sched("scan", chunk_t=4, pipeline_depth=3)
    rng = np.random.default_rng(5)
    for i in range(3):
        sched.submit(Request(
            f"w{i}", rng.normal(size=(9,)).astype(np.float32)))
    for _ in range(6):  # warmup: chunk + decode programs both exercised
        sched.step()
    warm = set(sched.stats()["programs"])
    for i in range(3):
        sched.feed(f"w{i}", rng.normal(size=(3,)).astype(np.float32))
        sched.close(f"w{i}")
    sched.drain()
    assert set(sched.stats()["programs"]) == warm
    assert sched.stats()["pipeline_depth"] == 3


def test_pipeline_depth_validation_and_latency_override():
    with pytest.raises(ValueError):
        _mk_sched("scan", pipeline_depth=0)
    # measure_latency=True overrides the pipeline: every call retires
    # synchronously within its own tick, so nothing stays in flight
    sched = _mk_sched("scan", pipeline_depth=4, measure_latency=True)
    sched.submit(Request("a", np.ones((20,), np.float32)))
    for _ in range(4):
        sched.step()
        assert sched.stats()["inflight_calls"] == 0
    assert all(c["sync"] for c in sched.call_log)


def test_pipeline_depth_bounds_inflight_queue():
    """A depth-d scheduler never holds more than d dispatched calls
    after a tick completes (the depth cap is enforced even when
    opportunistic retirement finds nothing ready)."""
    sched = _mk_sched("scan", chunk_t=2, pipeline_depth=2)
    rng = np.random.default_rng(11)
    for i in range(4):
        sched.submit(Request(
            f"b{i}", rng.normal(size=(20,)).astype(np.float32)))
        sched.close(f"b{i}")
    while sched.runs or sched.queued_total:
        sched.step()
        assert len(sched._inflight) <= 2
    sched._flush()


# ------------------------------------------- columnar call accounting
class _PerMemberLoop(BatchingScheduler):
    """The oracle: a retired call accounted member by member, as the
    scheduler did before its counters became rows — each member's
    `RequestStats` updated in place, per-detector dicts merged, its
    rows copied, one publish per member."""

    def _fold(self, run):
        pass  # the per-member loop keeps RequestStats current itself

    def _account(self, inf, outlier, ecc, scores, events):
        wall = (inf.sync_wall if inf.sync_wall is not None
                else time.perf_counter() - inf.t0)
        members = list(zip(inf.runs, inf.slots.tolist(), inf.ns.tolist()))
        retired = int(sum(n for _, _, n in members))
        self.call_log.append({
            "kind": "fused", "t": inf.t_len, "slots": len(members),
            "retired": retired, "wall_s": wall,
            "sync": inf.sync_wall is not None})
        self._h_wall.observe(wall * 1e3, weight=max(retired, 1))
        self._c_samples.inc(retired)
        flagged = (events["flagged"] if events is not None
                   else self._deferred_flagged)
        slots = np.array([s for _, s, _ in members], np.intp)
        t_max = max(n for _, _, n in members)
        cols = np.ascontiguousarray(outlier[:t_max, slots].T)
        n_flags = cols.sum(axis=1).tolist()
        names = self._det_names
        if self._ensemble:
            # gathered first when the call holds few of the pool's
            # slots, else reduced first and then picked
            few = 4 * len(slots) < ecc.shape[1]
            pick = slice(None) if few else slots
            bits = ecc[:t_max, slots] if few else ecc[:t_max]
            counts = np.stack([np.count_nonzero(bits & (1 << d), axis=0)
                               for d in range(len(names))])[:, pick]
            for d, det in enumerate(names):
                if int(counts[d].sum()):
                    self._det_counter(det).inc(int(counts[d].sum()))
            counts = counts.T.tolist()
            sc = (None if scores is None else
                  scores[:, :t_max, slots] if few else scores[:, :t_max])
            sums = (sc.sum(axis=1)[:, pick].T.tolist()
                    if scores is not None else None)
            rows = np.ascontiguousarray((bits if few else bits[:, slots]).T)
        for i, (run, slot, n) in enumerate(members):
            st = run.stats
            st.samples += n
            if len(st.chunk_latency_s) < self.latency_log_len:
                st.chunk_latency_s.append((wall, n))
            col = cols[i, :n]
            st.flags += n_flags[i]
            if n_flags[i]:
                flagged.append(run.req.rid)
                self._c_flags.inc(n_flags[i])
            det_counts = det_sums = None
            if self._ensemble:
                det_counts = {d: c for d, c in zip(names, counts[i]) if c}
                for det, c in det_counts.items():
                    st.det_flags[det] = st.det_flags.get(det, 0) + c
                if sums is not None:
                    det_sums = dict(zip(names, sums[i]))
                    for det, s in det_sums.items():
                        st.det_scores[det] = st.det_scores.get(det, 0.0) + s
            if n > 1:
                st.prefill_chunks += 1
            else:
                st.decode_steps += 1
            if self.collect:
                ecc_col = (rows[i, :n].copy() if self._ensemble
                           else ecc[:n, slot].copy())
                run.ecc_parts.append(ecc_col)
                run.outlier_parts.append(col)
            if self.events.active:
                data = {"slot": slot, "n": n, "flags": n_flags[i],
                        "dispatch_tick": inf.tick, "outlier": col.copy()}
                if inf.shard is not None:
                    data["shard"] = inf.shard
                if self.collect:
                    data["ecc"] = ecc_col.copy()
                if det_counts is not None:
                    data["det_flags"] = det_counts
                    data["detectors"] = names
                if det_sums is not None:
                    data["det_scores"] = det_sums
                self.events.publish("chunk_retired", self.tick_no,
                                    run.req.rid, **data)
            run.inflight -= 1
        self._g_inflight.set(len(self._inflight))


K5 = ("teda", "rde", "zscore", "hst", "teda-q")
SUBSETS = [None, ("teda", "rde"), ("hst",), ("zscore", "teda-q", "rde")]


def _skewed_rids(n):
    """n rids that the two-shard ring routes to shard 0."""
    from repro.engine import ShardedPool
    probe = ShardedPool("scan", shards=2, buckets=(8,))
    return [rid for rid in (f"skew{i}" for i in range(200))
            if probe.route(rid) == 0][:n]


COLUMNAR = {
    # case: (backend, scheduler options, workload options)
    "scan-collect": ("scan", dict(collect=True), {}),
    "scan-nocollect": ("scan", dict(collect=False), {}),
    "pallas-q-collect": ("pallas-q", dict(collect=True), {}),
    "pallas-q-nocollect": ("pallas-q", dict(collect=False), {}),
    "ensemble-subsets-collect": (
        "ensemble", dict(collect=True, detectors=K5), dict(subsets=True)),
    "ensemble-subsets-nocollect": (
        "ensemble", dict(collect=False, detectors=K5), dict(subsets=True)),
    "sharded-migration": (
        "scan", dict(collect=True, shards=2, rebalance_every=2,
                     buckets=(8,)), dict(skew=True, seed=4)),
    "telemetry-midstream": (
        "pallas-q", dict(collect=True), dict(reads=(3, 6, 9))),
    "row-reuse": ("scan", dict(collect=True, buckets=(2,)), dict(n=7)),
}


def _serve_columnar(sched, specs, subsets=False, reads=()):
    """`_serve_interleaved`, with per-tenant detector subsets and
    `telemetry()` snapshots of every running request at the given
    ticks; returns the events and the snapshots."""
    sub = sched.subscribe(maxlen=1 << 16)
    order = list(specs)
    fed = {rid: 0 for rid in specs}
    closed = set()
    snaps = []
    for tick in range(500):
        if tick < len(order):
            rid = order[tick]
            h, live, m = specs[rid]
            kw = {}
            if subsets:
                i = order.index(rid)
                kw = dict(detectors=SUBSETS[i % len(SUBSETS)],
                          vote="any" if i % 2 else None)
            assert sched.submit(Request(rid, h, m=m, **kw))
            if not live.size:
                sched.close(rid)
                closed.add(rid)
        for rid, (h, live, m) in specs.items():
            if rid not in sched.stats_by_rid or rid in closed:
                continue
            if fed[rid] < live.size:
                sched.feed(rid, live[fed[rid]:fed[rid] + 1])
                fed[rid] += 1
            if fed[rid] == live.size:
                sched.close(rid)
                closed.add(rid)
        sched.step()
        if tick in reads:
            snaps.append({rid: _stats_values(sched.telemetry(rid))
                          for rid in sorted(sched.runs)})
        if sched.completed == len(specs):
            break
    sched.drain()
    return sub.poll(), snaps


def _stats_values(st):
    """A RequestStats as comparable values: score sums by their bits,
    latency pairs by their sample counts (walls differ run to run)."""
    d = asdict(st)
    d["det_scores"] = {k: float(v).hex() for k, v in d["det_scores"].items()}
    d["chunk_latency_s"] = [n for _, n in d["chunk_latency_s"]]
    return d


def _assert_same_payload(key, got, want, rid):
    if isinstance(want, np.ndarray):
        assert isinstance(got, np.ndarray) and not got.flags.writeable
        assert got.dtype == want.dtype, (rid, key)
        np.testing.assert_array_equal(got, want, err_msg=f"{rid} {key}")
    elif key == "det_scores":
        assert {k: float(v).hex() for k, v in got.items()} == {
            k: float(v).hex() for k, v in want.items()}, rid
    else:
        assert got == want, (rid, key)


@pytest.mark.parametrize("case", list(COLUMNAR))
def test_columnar_accounting_matches_per_member_loop(case):
    """Every event, `results()`, `RequestStats` and flag counter of the
    columnar accounting equals what the per-member loop gives, score
    sums bit for bit: Q and float backends, the ensemble with
    per-tenant subsets, `collect` on and off, a sharded pool migrating
    mid-stream, `telemetry()` reads mid-stream, and counter rows reused
    by later admissions."""
    backend, opts, wl = COLUMNAR[case]
    opts = dict(opts)
    opts.setdefault("buckets", (2, 4))
    specs = _workload(wl.get("n", 6), seed=wl.get("seed", 31))
    if wl.get("skew"):
        specs = dict(zip(_skewed_rids(len(specs)), specs.values()))
    runs = []
    for cls in (BatchingScheduler, _PerMemberLoop):
        sched = cls(backend, fmt=FMT, chunk_t=8, **opts)
        evs, snaps = _serve_columnar(sched, specs,
                                     subsets=wl.get("subsets", False),
                                     reads=wl.get("reads", ()))
        runs.append((sched, evs, snaps))
    (new, evs, snaps), (old, want_evs, want_snaps) = runs
    assert len(evs) == len(want_evs) > 0
    for ev, want in zip(evs, want_evs):
        assert (ev.kind, ev.seq, ev.tick, ev.rid) == (
            want.kind, want.seq, want.tick, want.rid)
        assert list(ev.data) == list(want.data), ev.kind
        for key, value in want.data.items():
            _assert_same_payload(key, ev.data[key], value, ev.rid)
    assert snaps == want_snaps
    if wl.get("reads"):
        assert any(snaps), "no request was running at a read"
    for rid in specs:
        assert _stats_values(new.stats_by_rid[rid]) == _stats_values(
            old.stats_by_rid[rid]), rid
        if opts["collect"]:
            got, want = new.results(rid), old.results(rid)
            for key in ("ecc", "outlier"):
                assert got[key].dtype == want[key].dtype
                np.testing.assert_array_equal(got[key], want[key])
    s_new, s_old = new.stats(), old.stats()
    assert s_new.get("detector_flags") == s_old.get("detector_flags")
    assert int(new._c_flags.value) == int(old._c_flags.value)
    assert new.stats()["completed"] == len(specs)
    if wl.get("skew"):
        assert new.pool.migrations > 0  # streams moved mid-stream
    if case == "row-reuse":
        assert new._rows.size < len(specs)  # completed rows re-taken


def test_stats_folds_counted_per_read():
    """`stats_folds` counts copies of a running request's row into its
    RequestStats: none on a tick nobody reads, one per completion, one
    per `telemetry()` or `stats_by_rid` read of a running request, none
    for a queued or finished one or a membership test."""
    sched = _mk_sched("scan", buckets=(2,), queue_limit=8,
                      measure_latency=True)
    folds = lambda: sched.stats()["stats_folds"]
    rng = np.random.default_rng(5)
    data = lambda n: rng.normal(size=(n,)).astype(np.float32)
    for i in range(3):
        sched.submit(Request(f"r{i}", data(20)))
    sched.close("r0")
    for _ in range(4):                     # r0 drains, r1 stays open
        sched.step()
        assert folds() == sched.completed  # the silent path
    assert sched.completed == 1 and folds() == 1
    assert sched.telemetry("r1").samples == 20
    assert folds() == 2                    # a running request
    sched.feed("r1", data(3))
    sched.step()
    st = sched.stats_by_rid["r1"]
    assert folds() == 3 and st.samples == 23
    sched.submit(Request("r3", data(4)))   # r1 and r2 hold the pool
    sched.step()
    assert sched.request_phase("r3") == "queued"
    sched.telemetry("r0")                  # finished: folded already
    sched.stats_by_rid["r3"]               # queued: no row yet
    assert "r1" in sched.stats_by_rid and len(list(sched.stats_by_rid))
    assert folds() == 3
    assert int(sched.registry.get("sched_stats_folds_total").labels(
        sched=sched.name).value) == 3
    for rid in ("r1", "r2", "r3"):
        sched.close(rid)
    sched.drain()
    assert folds() == 3 + 3                # one per completion
    assert sched.stats_by_rid["r3"].samples == 4
    assert folds() == 6


def test_counter_rows_grow_and_reuse():
    """`_Rows` keeps every row's counters when it doubles, and a row
    given back comes back zeroed."""
    from repro.launch.batching import _Rows
    rows = _Rows(k=2, capacity=2)
    taken = [rows.take() for _ in range(5)]   # grows 2 -> 4 -> 8
    assert taken == list(range(5)) and rows.samples.shape == (8,)
    idx = np.array(taken)
    rows.samples[idx] += idx + 1
    rows.det_scores[idx] += 0.5
    rows.scored[idx] = True
    for _ in range(4):
        rows.take()                           # grows 8 -> 16
    assert rows.det_flags.shape == (16, 2)
    np.testing.assert_array_equal(rows.samples[:5], [1, 2, 3, 4, 5])
    np.testing.assert_array_equal(rows.det_scores[:5], 0.5)
    rows.give(3)
    assert rows.take() == 3 and rows.size == 9
    assert (rows.samples[3], rows.det_scores[3, 1], rows.scored[3]) == (
        0, 0.0, False)
    assert rows.samples[4] == 5

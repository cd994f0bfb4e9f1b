"""Fleet-scale sharded pool acceptance suite (ISSUE 9).

The behavior contract under test: a K-shard `ShardedPool` is bit-exact
with a single-device `SlotPool` on the pallas-q path for ANY routing
and ANY migration schedule — sharding moves placement, never
arithmetic.  Around that contract: consistent-hash ring stability (a
fleet growing N→N+1 remaps <= 2/N of streams), live migration carrying
the ensemble aux column exactly, per-shard PoolFull backpressure that
leaves other shards' verdicts untouched, the sharded
`BatchingScheduler`/`serve_streams` path pinned deterministic across
runs and pipeline depths, and the virtual-device topology CI runs it
all on (`REPRO_VIRTUAL_DEVICES=8`).
"""
import numpy as np
import pytest

from helpers import given_or_cases, virtual_devices

from repro.engine import (HashRing, PoolFull, ShardedPool, SlotPool,
                          stable_hash)
from repro.fixedpoint import QFormat
from repro.launch.batching import BatchingScheduler, Request
from repro.launch.serve import serve_streams
from repro.obs import MetricsRegistry

FMT = QFormat(32, 20)


# ------------------------------------------------------------ hash ring
def test_stable_hash_is_process_stable():
    # pinned digests: a restart (new PYTHONHASHSEED) must not re-route
    assert stable_hash("tenant-a") == stable_hash("tenant-a")
    assert stable_hash("tenant-a") != stable_hash("tenant-b")
    assert 0 <= stable_hash("x") < 2 ** 64


def test_ring_assignment_is_deterministic_across_instances():
    a = HashRing(range(4))
    b = HashRing(range(4))
    keys = [f"r{i}" for i in range(500)]
    assert [a.assign(k) for k in keys] == [b.assign(k) for k in keys]


def test_ring_spreads_keys_over_every_shard():
    ring = HashRing(range(4))
    owners = {ring.assign(f"r{i}") for i in range(2000)}
    assert owners == {0, 1, 2, 3}


@given_or_cases(
    "n,seed", [(2, 0), (4, 1), (8, 2)],
    lambda st: {"n": st.integers(2, 12), "seed": st.integers(0, 99)},
    max_examples=20)
def test_ring_grow_remaps_at_most_2_over_n(n, seed):
    keys = [f"stream-{seed}-{i}" for i in range(3000)]
    ring = HashRing(range(n))
    before = {k: ring.assign(k) for k in keys}
    ring.add(n)
    moved = [k for k in keys if ring.assign(k) != before[k]]
    # ~1/(n+1) expected; 2/n is the generous stability bound the
    # ISSUE pins (vnodes smooth the arcs enough to hold it)
    assert len(moved) / len(keys) <= 2.0 / n
    # every moved key landed on the new shard — growth never shuffles
    # streams between the old shards
    assert all(ring.assign(k) == n for k in moved)


def test_ring_remove_only_moves_the_removed_shards_keys():
    ring = HashRing(range(4))
    keys = [f"r{i}" for i in range(1000)]
    before = {k: ring.assign(k) for k in keys}
    ring.remove(2)
    for k in keys:
        if before[k] != 2:
            assert ring.assign(k) == before[k]
        else:
            assert ring.assign(k) != 2


def test_ring_validation():
    ring = HashRing(range(2))
    with pytest.raises(ValueError, match="already on the ring"):
        ring.add(1)
    with pytest.raises(ValueError, match="not on the ring"):
        ring.remove(7)
    with pytest.raises(ValueError, match="vnodes"):
        HashRing(range(2), vnodes=0)
    with pytest.raises(ValueError, match="empty ring"):
        HashRing().assign("x")


# --------------------------------------------------- pool fundamentals
def test_sharded_pool_routes_and_places():
    pool = ShardedPool("scan", shards=3, buckets=(4, 8))
    for i in range(6):
        rid = f"r{i}"
        shard, slot = pool.acquire(rid)
        assert shard == pool.route(rid)
        assert pool.lookup(rid) == (shard, slot)
    assert pool.occupancy == 6
    assert sum(pool.occupancies()) == 6
    assert pool.imbalance == max(pool.occupancies()) - min(
        pool.occupancies())
    st = pool.stats()
    assert st["shards"] == 3 and st["occupancy"] == 6
    assert len(st["per_shard"]) == 3


def test_sharded_pool_validation():
    with pytest.raises(ValueError, match="shards"):
        ShardedPool("scan", shards=0)
    with pytest.raises(ValueError, match="rebalance_threshold"):
        ShardedPool("scan", shards=2, rebalance_threshold=1)
    pool = ShardedPool("scan", shards=2, buckets=(2,))
    pool.acquire("a")
    with pytest.raises(ValueError, match="already attached"):
        pool.acquire("a")
    with pytest.raises(ValueError, match="out of range"):
        pool.acquire("b", shard=5)
    with pytest.raises(KeyError, match="unknown stream"):
        pool.lookup("ghost")
    with pytest.raises(KeyError, match="unknown stream"):
        pool.release("ghost")
    with pytest.raises(ValueError, match="out of range"):
        pool.migrate("a", 9)


def test_release_frees_the_routed_shard():
    pool = ShardedPool("scan", shards=2, buckets=(2,))
    s, _ = pool.acquire("a")
    pool.release("a")
    assert pool.occupancy == 0
    # the slot is reusable on the same shard
    assert pool.acquire("a") == (s, 0) or pool.occupancy == 1


# ------------------------------------------- bit-exactness under churn
def _lockstep_compare(backend, seed, shards, fmt=None, chunks=4, t=8,
                      n_streams=6, **opts):
    """Feed identical streams to one SlotPool and one K-shard
    ShardedPool in lockstep, randomly migrating / detaching /
    re-attaching sharded streams between chunks; every surviving
    stream's outlier+ecc columns must match bit-for-bit."""
    rng = np.random.default_rng(seed)
    rids = [f"s{i}" for i in range(n_streams)]
    data = {}
    for i, rid in enumerate(rids):
        d = rng.normal(size=(chunks * t,)).astype(np.float32)
        if i % 2 == 0:
            d[chunks * t // 2] += 20.0  # loud burst: non-trivial flags
        data[rid] = d
    single = SlotPool(backend, buckets=(4, 8), fmt=fmt, **opts)
    sharded = ShardedPool(backend, shards=shards, buckets=(4, 8),
                          fmt=fmt, **opts)
    s_slots = {rid: int(single.acquire(1)[0]) for rid in rids}
    for rid in rids:
        sharded.acquire(rid)
    for c in range(chunks):
        if c:  # churn between chunks
            for _ in range(3):
                rid = rids[int(rng.integers(n_streams))]
                try:
                    sharded.migrate(rid, int(rng.integers(shards)))
                except PoolFull:
                    pass
            if rng.random() < 0.5:  # detach + cold re-attach, both pools
                rid = rids[int(rng.integers(n_streams))]
                single.release([s_slots[rid]])
                sharded.release(rid)
                s_slots[rid] = int(single.acquire(1)[0])
                sharded.acquire(rid)
        xs = np.zeros((t, single.capacity), np.float32)
        vl = np.zeros((single.capacity,), np.int32)
        for rid in rids:
            xs[:, s_slots[rid]] = data[rid][c * t:(c + 1) * t]
            vl[s_slots[rid]] = t
        ref = single.process(xs, valid_lens=vl)
        ref_out = np.asarray(ref["outlier"])
        ref_ecc = np.asarray(ref["ecc"])
        by_shard = {}
        for rid in rids:
            s, slot = sharded.lookup(rid)
            by_shard.setdefault(s, []).append((rid, slot))
        for s, members in sorted(by_shard.items()):
            cap = sharded.shard_capacity(s)
            x = np.zeros((t, cap), np.float32)
            v = np.zeros((cap,), np.int32)
            for rid, slot in members:
                x[:, slot] = data[rid][c * t:(c + 1) * t]
                v[slot] = t
            out = sharded.process_shard(s, x, valid_lens=v)
            got_out = np.asarray(out["outlier"])
            got_ecc = np.asarray(out["ecc"])
            for rid, slot in members:
                np.testing.assert_array_equal(
                    got_out[:, slot], ref_out[:, s_slots[rid]],
                    err_msg=f"outlier diverged for {rid} chunk {c}")
                np.testing.assert_array_equal(
                    got_ecc[:, slot], ref_ecc[:, s_slots[rid]],
                    err_msg=f"ecc diverged for {rid} chunk {c}")
    assert sharded.migrations > 0  # the schedule actually moved slots


@given_or_cases(
    "seed,shards", [(0, 2), (1, 3), (2, 4)],
    lambda st: {"seed": st.integers(0, 999),
                "shards": st.integers(2, 4)},
    max_examples=8)
def test_sharded_bitexact_pallas_q_under_migration_churn(seed, shards):
    """THE contract: K shards == one pool, exact Q-format bits, for a
    randomized routing + migration + attach/detach schedule."""
    _lockstep_compare("pallas-q", seed, shards, fmt=FMT,
                      interpret=True)


def test_sharded_bitexact_scan_backend():
    _lockstep_compare("scan", seed=7, shards=2)


# ------------------------------------------------------- live migration
def test_migrate_is_noop_to_same_shard():
    pool = ShardedPool("scan", shards=2, buckets=(4,))
    s, slot = pool.acquire("a")
    assert pool.migrate("a", s) == slot
    assert pool.migrations == 0


def test_migration_carries_ensemble_aux_exactly():
    """A mid-window zscore/ensemble slot keeps its aux state rows,
    per-slot m, detector weights and threshold bit-for-bit across the
    move — and its future verdicts match the unmigrated twin."""
    opts = dict(shards=2, buckets=(2, 4), block_t=8, interpret=True)
    moved = ShardedPool("ensemble", **opts)
    still = ShardedPool("ensemble", **opts)
    rng = np.random.default_rng(5)
    x = rng.normal(size=(24,)).astype(np.float32)
    x[17] += 25.0
    for pool in (moved, still):
        pool.acquire("a", m=2.5, detectors=("zscore", "teda"),
                     vote="any")

    def feed(pool, samples):
        s, slot = pool.lookup("a")
        cap = pool.shard_capacity(s)
        chunk = np.zeros((len(samples), cap), np.float32)
        vl = np.zeros((cap,), np.int32)
        chunk[:, slot] = samples
        vl[slot] = len(samples)
        out = pool.process_shard(s, chunk, valid_lens=vl)
        return (np.asarray(out["outlier"])[:, slot],
                np.asarray(out["ecc"])[:, slot])

    feed(moved, x[:12]), feed(still, x[:12])  # mid-window warm state
    src_s, src_slot = moved.lookup("a")
    eng = moved.pools[src_s].engine
    pre = {
        "k": np.asarray(eng.state.k)[src_slot],
        "mean": np.asarray(eng.state.mean)[src_slot],
        "var": np.asarray(eng.state.var)[src_slot],
        "aux": np.asarray(eng.state.aux)[:, src_slot].copy(),
        "m": eng._m[src_slot],
        "det_w": eng._det_w[:, src_slot].copy(),
        "det_thr": eng._det_thr[src_slot],
    }
    assert pre["aux"].any()  # mid-window: zscore aux is warm, not zero
    dst = 1 - src_s
    new_slot = moved.migrate("a", dst)
    deng = moved.pools[dst].engine
    np.testing.assert_array_equal(
        np.asarray(deng.state.k)[new_slot], pre["k"])
    np.testing.assert_array_equal(
        np.asarray(deng.state.mean)[new_slot], pre["mean"])
    np.testing.assert_array_equal(
        np.asarray(deng.state.var)[new_slot], pre["var"])
    np.testing.assert_array_equal(
        np.asarray(deng.state.aux)[:, new_slot], pre["aux"])
    assert deng._m[new_slot] == pre["m"]
    np.testing.assert_array_equal(deng._det_w[:, new_slot],
                                  pre["det_w"])
    assert deng._det_thr[new_slot] == pre["det_thr"]
    # verdicts after the move == the twin that never moved
    out_m, ecc_m = feed(moved, x[12:])
    out_s, ecc_s = feed(still, x[12:])
    np.testing.assert_array_equal(out_m, out_s)
    np.testing.assert_array_equal(ecc_m, ecc_s)
    assert out_m.any()  # the burst at x[17] actually flagged


def test_one_device_per_shard_pins_engine_state():
    """devices= with one device per shard places each shard's state on
    its device (no mesh, so the ensemble backend shards too): the state
    stays committed there through a bucket resize and a migration, and
    verdicts equal the unpinned pool's."""
    import jax
    dev = jax.devices()[0]
    opts = dict(shards=2, buckets=(2, 4), block_t=8, interpret=True)
    pinned = ShardedPool("ensemble", devices=[dev, dev], **opts)
    plain = ShardedPool("ensemble", **opts)
    rng = np.random.default_rng(9)
    x = rng.normal(size=(16, 3)).astype(np.float32)
    x[11, 1] += 25.0
    outs = []
    for pool in (pinned, plain):
        for r in ("a", "b", "c"):
            pool.acquire(r, shard=0)  # 3 streams: shard 0 grows to 4
        pool.migrate("b", 1)
        got = {}
        for s in (0, 1):
            cap = pool.shard_capacity(s)
            chunk = np.zeros((16, cap), np.float32)
            vl = np.zeros((cap,), np.int32)
            for j, r in enumerate(("a", "b", "c")):
                rs, slot = pool.lookup(r)
                if rs == s:
                    chunk[:, slot], vl[slot] = x[:, j], 16
            out = np.asarray(pool.process_shard(s, chunk,
                                                valid_lens=vl)["outlier"])
            for j, r in enumerate(("a", "b", "c")):
                rs, slot = pool.lookup(r)
                if rs == s:
                    got[r] = out[:, slot]
        outs.append(got)
    for r in ("a", "b", "c"):
        np.testing.assert_array_equal(outs[0][r], outs[1][r])
    assert outs[0]["b"].any()  # the burst actually flagged
    for p in pinned.pools:
        for eng in p._engines.values():
            assert eng.device == dev
            assert all(a.committed and a.devices() == {dev}
                       for a in eng.state if a is not None)


def test_migrate_to_full_shard_leaves_stream_in_place():
    pool = ShardedPool("scan", shards=2, buckets=(2,))
    pool.acquire("a", shard=0)
    pool.acquire("b", shard=1)
    pool.acquire("c", shard=1)  # shard 1 now at its top bucket
    with pytest.raises(PoolFull, match="migration target shard 1"):
        pool.migrate("a", 1)
    assert pool.lookup("a")[0] == 0  # untouched
    assert pool.migrations == 0


def test_rebalancer_flattens_occupancy_deterministically():
    pool = ShardedPool("scan", shards=2, buckets=(8,))
    for i in range(6):
        pool.acquire(f"r{i}", shard=0)
    assert pool.occupancies() == [6, 0]
    moves = pool.rebalance()
    assert pool.imbalance < pool.rebalance_threshold
    # deterministic candidate order: lexicographically smallest rids
    assert [m[0] for m in moves] == ["r0", "r1"] or len(moves) >= 2
    twin = ShardedPool("scan", shards=2, buckets=(8,))
    for i in range(6):
        twin.acquire(f"r{i}", shard=0)
    assert twin.rebalance() == moves


def test_rebalancer_respects_avoid_set():
    pool = ShardedPool("scan", shards=2, buckets=(8,))
    for i in range(4):
        pool.acquire(f"r{i}", shard=0)
    moves = pool.rebalance(avoid={f"r{i}" for i in range(4)})
    assert moves == []  # everything movable pinned: try next tick
    assert pool.occupancies() == [4, 0]


def test_migration_metrics_and_events():
    reg = MetricsRegistry()
    from repro.obs import EventBus
    bus = EventBus()
    seen = []
    bus.attach(seen.append)
    pool = ShardedPool("scan", shards=2, buckets=(4,),
                       registry=reg, events=bus)
    pool.acquire("a", shard=0)
    pool.migrate("a", 1, tick=42)
    assert pool.migrations == 1
    ev = [e for e in seen if e.kind == "shard_migrated"]
    assert len(ev) == 1
    assert ev[0].rid == "a" and ev[0].tick == 42
    assert ev[0].data["src"] == 0 and ev[0].data["dst"] == 1
    snap = reg.snapshot()
    assert any("sharded_migrations_total" in k for k in snap)


# --------------------------------------------- per-shard backpressure
def test_pool_full_on_one_shard_spares_the_others():
    """Filling one shard's ladder backpressures streams routed there
    and does not perturb another shard's verdicts by one bit."""
    pool = ShardedPool("scan", shards=2, buckets=(2,))
    by_shard = {0: [], 1: []}
    i = 0
    while len(by_shard[0]) < 3 or len(by_shard[1]) < 1:
        rid = f"t{i}"
        by_shard[pool.route(rid)].append(rid)
        i += 1
    for rid in by_shard[0][:2]:
        pool.acquire(rid)
    lone = by_shard[1][0]
    pool.acquire(lone)
    with pytest.raises(PoolFull, match="shard 0"):
        pool.acquire(by_shard[0][2])  # shard 0 ladder is full
    # shard 1's stream serves bit-exact with a solo single pool
    rng = np.random.default_rng(3)
    x = rng.normal(size=(16,)).astype(np.float32)
    x[11] += 30.0
    solo = SlotPool("scan", buckets=(2,))
    solo_slot = int(solo.acquire(1)[0])
    s, slot = pool.lookup(lone)
    cap = pool.shard_capacity(s)
    chunk = np.zeros((16, cap), np.float32)
    vl = np.zeros((cap,), np.int32)
    chunk[:, slot] = x
    vl[slot] = 16
    ref = np.zeros((16, solo.capacity), np.float32)
    rvl = np.zeros((solo.capacity,), np.int32)
    ref[:, solo_slot] = x
    rvl[solo_slot] = 16
    got = pool.process_shard(s, chunk, valid_lens=vl)
    want = solo.process(ref, valid_lens=rvl)
    np.testing.assert_array_equal(
        np.asarray(got["outlier"])[:, slot],
        np.asarray(want["outlier"])[:, solo_slot])


# ------------------------------------------------- sharded scheduler
def _interleave(sched, specs, max_ticks=500):
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
        if len(closed) == len(specs):
            break
        sched.step()
    sched.drain()


def _churn_specs(n, seed):
    rng = np.random.default_rng(seed)
    specs = {}
    for i in range(n):
        h = rng.normal(size=(int(rng.integers(4, 24)),)).astype(
            np.float32)
        live = rng.normal(size=(int(rng.integers(0, 8)),)).astype(
            np.float32)
        if live.size and i % 3 == 0:
            live[live.size // 2] += 25.0
        specs[f"r{i}"] = (h, live, [1.5, 3.0, 6.0][i % 3])
    return specs


def test_sharded_scheduler_bitexact_with_single_pool():
    """The scheduler contract on the Q path: shards=2 with forced
    rebalancer migrations returns the same per-sample verdict bits as
    the single-pool scheduler."""
    specs = _churn_specs(6, seed=11)
    kw = dict(buckets=(2, 4), chunk_t=8, fmt=FMT, interpret=True,
              collect=True, measure_latency=False)
    single = BatchingScheduler("pallas-q", **kw)
    sharded = BatchingScheduler("pallas-q", shards=2,
                                rebalance_every=2, **kw)
    _interleave(single, specs)
    _interleave(sharded, specs)
    for rid in specs:
        a = single.results(rid)
        b = sharded.results(rid)
        np.testing.assert_array_equal(
            a["outlier"], b["outlier"],
            err_msg=f"verdicts diverged for {rid}")
        np.testing.assert_array_equal(a["ecc"], b["ecc"])
    st = sharded.stats()
    assert st["shards"] == 2
    assert st["pool"]["shards"] == 2


def test_sharded_scheduler_rebalances_under_skew():
    """Rids hand-picked onto one ring shard: the rebalancer must move
    some mid-run, and verdicts must still match the single pool."""
    probe = ShardedPool("scan", shards=2, buckets=(8,))
    rng = np.random.default_rng(4)
    rids, i = [], 0
    while len(rids) < 5:
        if probe.route(f"skew{i}") == 0:
            rids.append(f"skew{i}")
        i += 1
    specs = {rid: (rng.normal(size=(12,)).astype(np.float32),
                   rng.normal(size=(4,)).astype(np.float32), 3.0)
             for rid in rids}
    kw = dict(buckets=(8,), chunk_t=8, collect=True,
              measure_latency=False)
    single = BatchingScheduler("scan", **kw)
    sharded = BatchingScheduler("scan", shards=2, rebalance_every=2,
                                **kw)
    _interleave(single, specs)
    _interleave(sharded, specs)
    assert sharded.pool.migrations > 0  # skew actually triggered moves
    assert sharded.stats()["migrations"] > 0
    for rid in specs:
        np.testing.assert_array_equal(
            single.results(rid)["outlier"],
            sharded.results(rid)["outlier"])
    moved = [rid for rid in rids
             if sharded.telemetry(rid).migrations > 0]
    assert moved  # per-request telemetry recorded the moves


def test_sharded_scheduler_full_shard_blocks_only_that_class():
    """One shard's ladder filling up must not wedge admission for
    streams routed to shards with room."""
    probe = ShardedPool("scan", shards=2, buckets=(2,))
    on0 = [f"c{i}" for i in range(40) if probe.route(f"c{i}") == 0]
    on1 = [f"c{i}" for i in range(40) if probe.route(f"c{i}") == 1]
    sched = BatchingScheduler("scan", shards=2, buckets=(2,),
                              chunk_t=8, queue_limit=16,
                              collect=True, measure_latency=False)
    rng = np.random.default_rng(9)
    rids = on0[:3] + on1[:1]  # 3 onto the 2-slot shard + 1 elsewhere
    for rid in rids:
        assert sched.submit(Request(
            rid, rng.normal(size=(12,)).astype(np.float32)))
        sched.close(rid)
    sched.drain()
    assert sched.completed == len(rids)
    for rid in rids:
        assert sched.telemetry(rid).samples == 12


def test_scheduler_shard_validation():
    with pytest.raises(ValueError, match="shards"):
        BatchingScheduler("scan", shards=0)
    with pytest.raises(ValueError, match="rebalance_every"):
        BatchingScheduler("scan", shards=2, rebalance_every=-1)


# ------------------------------------------------ gateway determinism
def test_gateway_determinism_across_runs_and_depths():
    """serve_streams with sharding on: identical per-request flags and
    det_flags across two identical runs AND across pipeline_depth
    {1, 4} — pins the async+sharded path against nondeterministic
    retirement ordering."""
    rng = np.random.default_rng(21)
    streams = []
    for i in range(6):
        h = rng.normal(size=(10,)).astype(np.float32)
        lv = rng.normal(size=(6,)).astype(np.float32)
        if i % 2 == 0:
            lv[3] += 25.0
        streams.append((f"t{i}", h, lv, None))
    kw = dict(backend="scan", buckets=(2, 4), chunk_t=8, shards=2,
              rebalance_every=2, measure_latency=False)
    runs = [serve_streams(streams, pipeline_depth=1, **kw),
            serve_streams(streams, pipeline_depth=1, **kw),
            serve_streams(streams, pipeline_depth=4, **kw)]
    base = runs[0]
    assert base["shards"] == 2
    for other in runs[1:]:
        assert other["flagged"] == base["flagged"]
        for rid, pr in base["per_request"].items():
            opr = other["per_request"][rid]
            assert opr["flags"] == pr["flags"], rid
            assert opr["det_flags"] == pr["det_flags"], rid
            assert opr["samples"] == pr["samples"], rid


# ------------------------------------------------- virtual devices
def test_virtual_device_mesh_fanout_bitexact():
    """>= 4 virtual devices (REPRO_VIRTUAL_DEVICES=8 in CI): 2 shards
    x 2-device channel fan-out meshes must match the single-device
    pool exactly."""
    devs = virtual_devices(4)
    single = SlotPool("scan", buckets=(4, 8))
    pool = ShardedPool("scan", shards=2, buckets=(4, 8),
                       devices=devs[:4])
    rng = np.random.default_rng(13)
    rids = [f"v{i}" for i in range(5)]
    s_slots = {rid: int(single.acquire(1)[0]) for rid in rids}
    for rid in rids:
        pool.acquire(rid)
    x = rng.normal(size=(16, len(rids))).astype(np.float32)
    x[9, 0] += 30.0
    xs = np.zeros((16, single.capacity), np.float32)
    vl = np.zeros((single.capacity,), np.int32)
    for j, rid in enumerate(rids):
        xs[:, s_slots[rid]] = x[:, j]
        vl[s_slots[rid]] = 16
    ref = np.asarray(single.process(xs, valid_lens=vl)["outlier"])
    by_shard = {}
    for j, rid in enumerate(rids):
        s, slot = pool.lookup(rid)
        by_shard.setdefault(s, []).append((rid, slot, j))
    for s, members in by_shard.items():
        cap = pool.shard_capacity(s)
        chunk = np.zeros((16, cap), np.float32)
        v = np.zeros((cap,), np.int32)
        for rid, slot, j in members:
            chunk[:, slot] = x[:, j]
            v[slot] = 16
        got = np.asarray(pool.process_shard(
            s, chunk, valid_lens=v)["outlier"])
        for rid, slot, j in members:
            np.testing.assert_array_equal(got[:, slot],
                                          ref[:, s_slots[rid]])


def test_virtual_device_sharded_scheduler_end_to_end():
    devs = virtual_devices(4)
    specs = _churn_specs(5, seed=17)
    kw = dict(buckets=(4, 8), chunk_t=8, collect=True,
              measure_latency=False)
    single = BatchingScheduler("scan", **kw)
    sharded = BatchingScheduler("scan", shards=2, shard_devices=devs[:4],
                                rebalance_every=2, **kw)
    _interleave(single, specs)
    _interleave(sharded, specs)
    for rid in specs:
        np.testing.assert_array_equal(
            single.results(rid)["outlier"],
            sharded.results(rid)["outlier"])


def test_uneven_device_split_is_rejected():
    devs = virtual_devices(4)
    with pytest.raises(ValueError, match="split evenly"):
        ShardedPool("scan", shards=3, devices=devs[:4])
    with pytest.raises(ValueError, match="not divisible"):
        ShardedPool("scan", shards=2, buckets=(3, 6),
                    devices=devs[:4])

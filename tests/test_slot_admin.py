"""Slot admission and release: the host mirror of the active mask and
the slot updates folded into one jitted update.

`SlotPool` acquire / process / release / re-acquire sequences must
leave the packed state and the verdicts bit-for-bit where the pure
`engine_attach` / `engine_detach` put them; slot admin must read the
device's active mask only after a state is assigned from outside (one
counted fetch each, `engine_active_fetches_total`); and `slot_mask`
builds concrete masks on the host, with the bounds check it had.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import (EngineState, ShardedPool, SlotPool, StreamEngine,
                          engine_attach, engine_detach, engine_reset,
                          slot_mask)
from repro.fixedpoint import QFormat
from repro.obs import TickTracer, compile_watch

from helpers import active_fetches

FMT = QFormat(32, 20)
T = 8


def _opts(backend):
    return {"fmt": FMT} if backend == "pallas-q" else {}


def _repad(st: EngineState, cap: int) -> EngineState:
    """The reference re-pad: keep the common prefix of slots, zero (and
    deactivate) the rest."""
    keep = min(st.k.shape[0], cap)

    def pad(v):
        v = np.asarray(v)
        out = np.zeros(v.shape[:-1] + (cap,), v.dtype)
        out[..., :keep] = v[..., :keep]
        return jnp.asarray(out)

    return EngineState(k=pad(st.k), mean=pad(st.mean), var=pad(st.var),
                       active=pad(st.active),
                       aux=None if st.aux is None else pad(st.aux))


def _same_bits(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    assert a.tobytes() == b.tobytes(), what


@pytest.mark.parametrize("buckets", [(32,), (8, 16, 32)],
                         ids=["one-bucket", "ladder"])
@pytest.mark.parametrize("backend", ["scan", "pallas-q", "ensemble"])
def test_pool_slot_admin_matches_pure_updates(backend, buckets):
    """A seeded acquire / process / release / re-acquire sequence on a
    `SlotPool` equals the same sequence through the pure
    `engine_attach` / `engine_detach` (re-padded by hand where the pool
    changes bucket, processed by a plain engine of that capacity): k,
    mean, var, active, aux bits and every verdict."""
    rng = np.random.default_rng(14)
    pool = SlotPool(backend, buckets=buckets, block_t=T, **_opts(backend))
    plain = {}
    ref = _repad(pool.engine.state, pool.capacity)
    resizes = 0

    def check():
        st = pool.engine.state
        assert ref.k.shape[0] == pool.capacity
        for f in EngineState._fields:
            if getattr(ref, f) is None:
                assert getattr(st, f) is None, f
            else:
                _same_bits(getattr(st, f), getattr(ref, f), f)
        assert pool.engine.active_mask.tolist() == \
            np.asarray(ref.active).tolist()

    def acquire(n):
        nonlocal ref, resizes
        free = np.flatnonzero(~np.asarray(ref.active))
        need = int(np.asarray(ref.active).sum()) + n
        idx = pool.acquire(n)
        if pool.capacity != ref.k.shape[0]:
            ref = _repad(ref, pool.capacity)
            free = np.flatnonzero(~np.asarray(ref.active))
            resizes += 1
        assert need <= pool.capacity
        assert idx.tolist() == free[:n].tolist()
        ref = engine_attach(ref, idx)
        check()

    def release(slots):
        nonlocal ref, resizes
        pool.release(slots)
        ref = engine_detach(ref, slots)
        if pool.capacity != ref.k.shape[0]:
            ref = _repad(ref, pool.capacity)
            resizes += 1
        check()

    def process():
        nonlocal ref
        cap = pool.capacity
        x = rng.normal(size=(T, cap)).astype(np.float32)
        x[rng.integers(0, T), rng.integers(0, cap)] += 25.0
        vl = rng.integers(0, T + 1, size=cap).astype(np.int32)
        out = pool.process(x, valid_lens=vl)
        eng = plain.get(cap)
        if eng is None:
            eng = plain[cap] = StreamEngine(cap, backend, block_t=T,
                                            auto_attach=False,
                                            **_opts(backend))
        eng.state = ref
        want = eng.process(x, valid_lens=vl)
        ref = eng.state
        assert sorted(out) == sorted(want)
        for key in out:
            _same_bits(out[key], want[key], key)
        check()

    def live():
        return np.flatnonzero(np.asarray(ref.active))

    acquire(6)
    process()
    acquire(7)                                  # 13 live: bucket 16
    process()
    acquire(6)                                  # 19 live: bucket 32
    process()
    release(rng.choice(live(), size=9, replace=False))
    process()
    acquire(5)                                  # re-acquire freed slots
    process()
    release([s for s in live() if s >= 8])      # ladder shrinks to 8
    process()
    acquire(4)
    process()
    assert pool.resizes == resizes
    assert resizes >= (3 if len(buckets) > 1 else 0)


@pytest.mark.parametrize("backend", ["scan", "pallas-q", "ensemble"])
def test_updates_between_reads_fold_into_one(backend):
    """attach, detach and reset only touch the host until the state is
    read; the one update then leaves the state the calls one by one
    leave, the same slot touched several times included."""
    rng = np.random.default_rng(41)
    cap = 16
    eng = StreamEngine(cap, backend, block_t=T, auto_attach=False,
                       **_opts(backend))
    eng.attach(np.arange(0, cap, 2))
    eng.process(rng.normal(size=(T, cap)).astype(np.float32))
    ref = eng.state
    for _ in range(3):
        held = eng._state
        for _ in range(12):
            op = rng.integers(3)
            live = np.flatnonzero(np.asarray(ref.active))
            free = np.flatnonzero(~np.asarray(ref.active))
            if op == 0 and len(free):
                slots = rng.choice(free, size=min(3, len(free)),
                                   replace=False)
                eng.attach(slots)
                ref = engine_attach(ref, slots)
            elif op == 1 and len(live):
                slots = rng.choice(live, size=min(2, len(live)),
                                   replace=False)
                eng.detach(slots)
                ref = engine_detach(ref, slots)
            else:
                slots = rng.choice(cap, size=4, replace=False)
                eng.reset(slots)
                ref = engine_reset(ref, slots)
            assert eng._state is held          # nothing dispatched yet
            assert eng.active_mask.tolist() == \
                np.asarray(ref.active).tolist()
        st = eng.state
        for f in EngineState._fields:
            if getattr(ref, f) is None:
                assert getattr(st, f) is None, f
            else:
                _same_bits(getattr(st, f), getattr(ref, f), f)
        x = rng.normal(size=(T, cap)).astype(np.float32)
        out = eng.process(x)
        plain = StreamEngine(cap, backend, block_t=T, auto_attach=False,
                             **_opts(backend))
        plain.state = ref
        want = plain.process(x)
        for key in out:
            _same_bits(out[key], want[key], key)
        ref = eng.state


def test_pool_records_the_spans_it_declares():
    """`SlotPool.SPANS` names what a traced pool records besides JAX's
    `compile`s, one span per call: `acquire` and `release`, with `n`,
    `pool` and `resized`."""
    tr = TickTracer(capacity=64)
    pool = SlotPool("scan", buckets=(2, 4), tracer=tr, name="p")
    a = pool.acquire(2)
    b = pool.acquire(1)                         # grows 2 -> 4
    pool.release(b)                             # shrinks 4 -> 2
    pool.release(a[:1])
    spans = [(e["name"], e["args"]) for e in tr.events()
             if e["ph"] == "X" and e["name"] != "compile"]
    assert {n for n, _ in spans} == set(SlotPool.SPANS)
    assert [(n, a["n"], a["pool"], a["resized"]) for n, a in spans] == [
        ("acquire", 2, "p", False), ("acquire", 1, "p", True),
        ("release", 1, "p", True), ("release", 1, "p", False)]


def test_active_mask_is_not_fetched_at_one_bucket():
    """500 acquire / process / release rounds at one bucket never read
    the device's active mask, and slot admin compiles nothing."""
    pool = SlotPool("scan", buckets=(8,))
    pool.acquire(3)
    x = np.zeros((2, 8), np.float32)
    pool.process(x, valid_lens=np.ones((8,), np.int32))  # compiles
    mark = compile_watch().mark()
    before = active_fetches(pool.registry)
    for i in range(500):
        slot = pool.acquire(1)
        pool.process(x, valid_lens=np.full((8,), i % 3, np.int32))
        pool.release(slot)
        assert pool.occupancy == 3
    assert active_fetches(pool.registry) == before == 0
    assert pool.free_slots.tolist() == [3, 4, 5, 6, 7]
    assert compile_watch().since(mark)["compiles"] == 0


def test_active_mask_fetched_once_per_resize():
    """A re-pad assigns the new bucket's state from outside: its mirror
    is read back once, and no more."""
    pool = SlotPool("scan", buckets=(2, 4))
    pool.acquire(2)
    x = np.zeros((2, 2), np.float32)
    for cycle in range(3):
        grown = pool.acquire(1)                 # 2 -> 4
        assert pool.capacity == 4
        pool.process(np.zeros((2, 4), np.float32))
        assert active_fetches(pool.registry) == pool.resizes == 2 * cycle + 1
        pool.release(grown)                     # 4 -> 2
        assert pool.capacity == 2 and pool.occupancy == 2
        pool.process(x)
        assert active_fetches(pool.registry) == pool.resizes == 2 * cycle + 2


def test_active_mask_fetched_once_per_migration():
    pool = ShardedPool("scan", shards=2, buckets=(4,))
    for i in range(4):
        pool.acquire(f"s{i}", shard=0)
    assert active_fetches(pool.registry) == 0
    for n, rid in enumerate(["s0", "s1", "s2"], start=1):
        pool.migrate(rid, 1)
        pool.process_shard(1, np.zeros((2, 4), np.float32))
        assert pool.pools[1].occupancy == n
        assert active_fetches(pool.registry) == n


def test_outside_state_assignment_refreshes_mirror():
    eng = StreamEngine(4, "scan", auto_attach=False)
    eng.attach([1])
    st = eng.state
    eng.state = st._replace(active=jnp.asarray([True, True, False, True]))
    assert eng.active_slots.tolist() == [0, 1, 3]
    assert eng.attach(n=1).tolist() == [2]
    assert active_fetches(eng.registry) == 1
    with pytest.raises(ValueError):             # the mirror is read-only
        eng.active_mask[0] = False


def _old_slot_mask(slots, capacity):
    """`slot_mask` as it was: a device scatter, after the host bounds
    check."""
    if slots is None:
        return jnp.ones((capacity,), bool)
    slots = jnp.asarray(slots)
    if slots.dtype == bool:
        return slots.reshape((capacity,))
    idx = np.asarray(slots)
    if idx.size and (idx.min() < 0 or idx.max() >= capacity):
        raise IndexError(
            f"slot indices {np.unique(idx).tolist()} out of range for "
            f"capacity {capacity}")
    return jnp.zeros((capacity,), bool).at[slots].set(True)


@pytest.mark.parametrize("slots", [
    None, [3], [0, 7, 2], (5, 5, 1), np.array([6, 4]), jnp.array([1, 2]),
    np.int64(4), np.array([], np.int64),
    np.array([True, False] * 4), jnp.arange(8) % 3 == 0,
])
def test_slot_mask_matches_device_scatter(slots):
    got = slot_mask(slots, 8)
    assert isinstance(got, np.ndarray) and got.dtype == bool
    assert got.tolist() == np.asarray(_old_slot_mask(slots, 8)).tolist()


@pytest.mark.parametrize("slots", [[-1, 3], [8], np.array([2, 9, 9])])
def test_slot_mask_bounds_error_unchanged(slots):
    with pytest.raises(IndexError) as old:
        _old_slot_mask(slots, 8)
    with pytest.raises(IndexError) as new:
        slot_mask(slots, 8)
    assert str(new.value) == str(old.value)


def test_slot_mask_traced_under_jit():
    f = jax.jit(lambda s: slot_mask(s, 8))
    assert np.asarray(f(jnp.array([1, 5]))).tolist() == \
        np.asarray(_old_slot_mask([1, 5], 8)).tolist()
    mask = np.array([True, False] * 4)
    assert np.asarray(f(mask)).tolist() == mask.tolist()

"""Substrate tests: optimizer, checkpointing, data pipeline, sharding rules."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import PrefetchIterator, TokenStream, batch_stats
from repro.core.guard import GuardConfig
from repro.optim import adamw


# ------------------------------------------------------------ optimizer --
def _params():
    return {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))}


def test_adamw_decreases_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=100,
                            weight_decay=0.0)
    params = {"w": jnp.asarray(5.0)}
    state = adamw.init(params)

    def loss(p):
        return (p["w"] - 1.0) ** 2

    for _ in range(60):
        g = jax.grad(loss)(params)
        params, state, _ = adamw.update(g, state, params, cfg)
    assert abs(float(params["w"]) - 1.0) < 0.3


def test_adamw_skip_is_noop():
    cfg = adamw.AdamWConfig()
    params = _params()
    state = adamw.init(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    new_p, new_s, m = adamw.update(grads, state, params, cfg, skip=True)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.all(a == b)), new_p, params))
    assert int(new_s.count) == 0
    assert float(m["skipped"]) == 1.0


def test_adamw_clipping():
    cfg = adamw.AdamWConfig(clip_norm=1.0)
    params = _params()
    state = adamw.init(params)
    grads = jax.tree_util.tree_map(lambda p: 1e6 * jnp.ones_like(p), params)
    new_p, _, m = adamw.update(grads, state, params, cfg)
    assert float(m["grad_norm"]) > 1e5
    delta = float(jnp.max(jnp.abs(new_p["w"] - params["w"])))
    assert delta < 1.0  # clipped update stays bounded


def test_schedule_warmup_and_decay():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_frac=0.1)
    lr1 = float(adamw.schedule(cfg, jnp.asarray(1)))
    lr10 = float(adamw.schedule(cfg, jnp.asarray(10)))
    lr100 = float(adamw.schedule(cfg, jnp.asarray(100)))
    assert lr1 < lr10
    assert abs(lr10 - 1.0) < 1e-5
    assert abs(lr100 - 0.1) < 1e-2


# ----------------------------------------------------------- checkpoint --
def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "count": jnp.asarray(7)}
    mgr.save(5, state)
    assert mgr.latest_step() == 5
    restored, meta = mgr.restore(state)
    np.testing.assert_allclose(restored["params"]["w"],
                               np.arange(6.0).reshape(2, 3))
    assert meta["step"] == 5


def test_checkpoint_keep_k_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    state = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.full(3, float(s))})
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(dirs) == 2  # keep-K gc
    restored, meta = mgr.restore(state)
    assert meta["step"] == 4
    np.testing.assert_allclose(restored["x"], 4.0)


def test_checkpoint_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
    mgr.save(1, {"x": jnp.ones(4)})
    mgr.wait()
    assert mgr.latest_step() == 1


def test_checkpoint_shape_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"x": jnp.ones((2, 2))})
    with pytest.raises(ValueError, match="shape mismatch"):
        mgr.restore({"x": jnp.ones((3, 3))})


def test_checkpoint_elastic_resharding(tmp_path):
    """Restore onto explicit (new-mesh) shardings."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    state = {"x": jnp.arange(8.0)}
    mgr.save(1, state)
    mesh = jax.make_mesh((1,), ("data",), (jax.sharding.AxisType.Auto,))
    sh = {"x": jax.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))}
    restored, _ = mgr.restore(state, shardings=sh)
    assert restored["x"].sharding.is_equivalent_to(sh["x"], 1)


# ------------------------------------------------------------------ data --
def test_tokenstream_deterministic_and_indexable():
    s = TokenStream(1000, 4, 32, seed=3)
    a = s.batch_at(10)["tokens"]
    b = s.batch_at(10)["tokens"]
    np.testing.assert_array_equal(a, b)
    assert a.shape == (4, 33)
    assert a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 1000
    it = iter(s)
    first = next(it)["tokens"]
    np.testing.assert_array_equal(first, s.batch_at(0)["tokens"])


def test_tokenstream_corrupt_every():
    s = TokenStream(100, 2, 16, corrupt_every=5)
    assert (s.batch_at(5)["tokens"] == 99).all()
    assert not (s.batch_at(4)["tokens"] == 99).all()


def test_prefetch_screen_drops_corrupt():
    # corruption starts after warmup AND after k > m^2: since eq (3)'s
    # variance absorbs the current sample, zeta <= (k+1)/(2k), so eq (6)
    # with m is untrippable until k > m^2 (see DESIGN.md §7) — an earlier
    # spike slips through and contaminates the stats.
    src = (TokenStream(100, 2, 16, corrupt_every=10).batch_at(i)
           for i in range(40))
    it = PrefetchIterator(src, depth=2,
                          screen=GuardConfig(m=3.0, warmup_steps=6,
                                             channels=2))
    batches = list(it)
    assert it.dropped >= 3  # corrupt batches screened out post-warmup
    assert all(not (b["tokens"] == 99).all() for b in batches)


def test_batch_stats_shape():
    s = batch_stats({"tokens": np.ones((2, 8), np.int32)})
    assert s.shape == (2,)


# ------------------------------------------------------- sharding rules --
def test_param_spec_rules():
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AbstractMesh
    from repro.sharding.rules import param_spec
    mesh = AbstractMesh((16, 16), ("data", "model"))
    # big 2D up-projection: FSDP in, TP out
    assert param_spec(mesh, "blocks_0/mlp/wi/w", (48, 8192, 22016)) == \
        P(None, "data", "model")
    # down-projection: contracting dim on model
    assert param_spec(mesh, "blocks_0/mlp/wo/w", (48, 22016, 8192)) == \
        P(None, "model", "data")
    # embedding: vocab on model
    assert param_spec(mesh, "embed/table", (128256, 4096)) == \
        P("model", "data")
    # experts: EP on E, FSDP on the ff dim (dispatch-intermediate
    # sharding — see rules.py)
    assert param_spec(mesh, "blocks_0/moe/wi", (48, 16, 6144, 10752)) == \
        P(None, "model", None, "data")
    assert param_spec(mesh, "blocks_0/moe/wo", (48, 16, 10752, 6144)) == \
        P(None, "model", "data", None)
    # experts: TP fallback when not divisible
    assert param_spec(mesh, "blocks_0/moe/wi", (32, 8, 4096, 14336)) == \
        P(None, None, "data", "model")
    # tiny arrays replicate
    assert param_spec(mesh, "final_norm/scale", (4096,)) == P()


def test_batch_and_cache_specs():
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AbstractMesh
    from repro.sharding.rules import batch_spec, cache_spec
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert batch_spec(mesh, 256) == P(("pod", "data"), None)
    assert batch_spec(mesh, 16) == P("data", None)
    # decode cache: batch shardable
    assert cache_spec(mesh, (32, 128, 32768, 8, 128)) == \
        P(None, ("pod", "data"), None, None, "model")
    # batch=1: context parallelism over sequence
    assert cache_spec(mesh, (13, 1, 524288, 4, 256)) == \
        P(None, None, "data", None, "model")

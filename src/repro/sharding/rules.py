"""Sharding rules: map every array in the system to a PartitionSpec.

Strategy (DESIGN.md §5):
  * batch/tokens         -> data-parallel over ("pod", "data")
  * 2D weights           -> FSDP on the input dim over "data", TP on the
                            output dim over "model" (down-projections
                            transpose this so the contracting dim stays
                            on "model")
  * embedding (vocab, d) -> vocab over "model" (sharded softmax/CE),
                            d over "data"
  * MoE expert stacks    -> expert-parallel over "model" when n_experts
                            divides the axis, else TP over d_ff
  * KV caches            -> batch over data when divisible, else sequence
                            over "data" (context parallelism, long_500k);
                            head_dim over "model" when divisible
  * tiny arrays (norms, biases, gates) -> replicated

Across pods parameters are replicated (DP over "pod"; FSDP stays inside a
pod where ICI is fast — grads cross DCN once per step). All rules are
*advisory*: pjit/GSPMD propagates them through the program.
"""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPLICATE_BELOW = 1 << 16  # arrays smaller than 64k entries: replicate

_DOWN_PROJ_NAMES = ("wo", "wdown", "wout")
_EXPERT_NAMES = ("wi", "wg", "wo")

# Experiment toggles for the §Perf hillclimb (repro.launch.hillclimb
# --rule-flag). Defaults = production baseline.
RULE_FLAGS = {
    "moe_prefer_tp": False,   # True: shard expert ff dim instead of EP
    "embed_data_shard": True,  # False: replicate embed d over data
    # True: parameter/optimizer FSDP spans the pod axis too (ZeRO-3
    # across pods — DCN all-gathers per step; the production choice for
    # >=100B-param models whose state cannot replicate per pod)
    "fsdp_over_pod": False,
}


def make_channel_fanout(fn, mesh: Mesh, axis_name: str = "data"):
    """shard_map fan-out of an independent-channel stream processor.

    `fn(x, k, mean, var, vlen, m) -> ((k', mean', var'),
    (ecc, outlier))` — the `repro.engine` backend contract: x is (T, C)
    with C independent univariate streams on the lane axis, the state
    rows (and the per-slot valid-length vector `vlen` and threshold
    vector `m`) are (C,) vectors, and the per-sample outputs are
    (T, C).  Channels are independent TEDA
    modules (the paper's replicated-module scaling, §5.2.1), so the
    fan-out needs no collectives: each device runs `fn` on its C/D
    channel slice.  The caller must keep C divisible by the axis size
    (StreamEngine asserts this).
    """
    vec = P(axis_name)
    row = P(None, axis_name)
    return jax.shard_map(
        fn, mesh=mesh, check_vma=False,
        in_specs=(row, vec, vec, vec, vec, vec),
        out_specs=((vec, vec, vec), (row, row)),
    )


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis(mesh: Mesh, name: str) -> int:
    return dict(mesh.shape)[name]  # works for Mesh and AbstractMesh


def _div(n: int, k: int) -> bool:
    return n % k == 0 and n >= k


def param_spec(mesh: Mesh, path: str, shape: Tuple[int, ...]) -> P:
    """Sharding rule for one parameter leaf, keyed on its tree path."""
    dsz, msz = _axis(mesh, "data"), _axis(mesh, "model")
    fsdp: object = "data"
    if RULE_FLAGS["fsdp_over_pod"] and "pod" in mesh.axis_names:
        fsdp = ("pod", "data")
        dsz = dsz * _axis(mesh, "pod")
    size = int(np.prod(shape)) if shape else 1
    if size < REPLICATE_BELOW or not shape:
        return P()
    parts = path.replace(".", "/").split("/")
    name = parts[-1]
    if name in ("w", "b") and len(parts) >= 2:  # dense leaf: use its module
        name = parts[-2]
    stacked = "blocks_" in path or "_blocks" in path  # leading groups dim
    off = 1 if stacked else 0
    dims = shape[off:]

    # embedding / unembedding tables
    if "table" in name or "embed" in path:
        d_ax = fsdp if (RULE_FLAGS["embed_data_shard"]
                        and _div(dims[1], dsz)) else None
        spec = [None] * off + ["model" if _div(dims[0], msz) else None,
                               d_ax]
        return P(*spec)

    # expert-stacked weights (E, din, dout)
    if "moe" in path and len(dims) == 3:
        e, din, dout = dims
        if _div(e, msz) and not RULE_FLAGS["moe_prefer_tp"]:
            # EP on E; FSDP on the ff dim so (E, C, ff) dispatch
            # intermediates shard over data instead of materializing per
            # expert-shard (wi/wg: ff is dim 2; wo: ff is dim 1)
            ff_dim = 2 if name in ("wi", "wg") else 1
            spec = [None] * 3
            spec[0] = "model"
            if _div(dims[ff_dim], dsz):
                spec[ff_dim] = fsdp
            return P(*([None] * off), *spec)
        # fall back to TP over the ff dim
        ff_dim = 2 if name in ("wi", "wg") else 1
        spec: list = [None] * (off + 3)
        if _div(dims[ff_dim], msz):
            spec[off + ff_dim] = "model"
        other = 1 if ff_dim == 2 else 2
        if _div(dims[other], dsz):
            spec[off + other] = fsdp
        return P(*spec)

    if len(dims) == 2:
        din, dout = dims
        if name in _DOWN_PROJ_NAMES:  # contracting dim on model
            return P(*([None] * off),
                     "model" if _div(din, msz) else None,
                     fsdp if _div(dout, dsz) else None)
        return P(*([None] * off),
                 fsdp if _div(din, dsz) else None,
                 "model" if _div(dout, msz) else None)

    if len(dims) == 1:
        return P(*([None] * off),
                 "model" if _div(dims[0], msz) else None)
    # conv kernels / recurrent blocks etc.
    spec = [None] * (off + len(dims))
    # shard the largest dim on model if possible
    big = int(np.argmax(dims))
    if _div(dims[big], msz):
        spec[off + big] = "model"
    return P(*spec)


def params_shardings(mesh: Mesh, params_tree):
    """NamedShardings for a whole param pytree (by tree path)."""

    def one(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                        for k in path)
        return NamedSharding(mesh, param_spec(mesh, pstr, leaf.shape))

    return jax.tree_util.tree_map_with_path(one, params_tree)


def batch_spec(mesh: Mesh, batch_size: int, kind: str = "train") -> P:
    """Spec for (B, S) token batches / (B,) decode tokens."""
    axes = dp_axes(mesh)
    total = int(np.prod([_axis(mesh, a) for a in axes]))
    if _div(batch_size, total):
        return P(axes) if kind == "decode" else P(axes, None)
    if "data" in axes and _div(batch_size, _axis(mesh, "data")):
        return P("data") if kind == "decode" else P("data", None)
    return P() if kind == "decode" else P(None, None)


def cache_spec(mesh: Mesh, shape: Tuple[int, ...], batch_axis: int = 1,
               seq_axis: int = 2, head_dim_axis: int = -1) -> P:
    """KV-cache spec: (groups, B, S, kv, hd)."""
    dsz, msz = _axis(mesh, "data"), _axis(mesh, "model")
    axes = dp_axes(mesh)
    total = int(np.prod([_axis(mesh, a) for a in axes]))
    spec = [None] * len(shape)
    b = shape[batch_axis]
    if _div(b, total):
        spec[batch_axis] = axes
    elif _div(b, dsz):
        spec[batch_axis] = "data"
    else:  # tiny batch: context-parallel over the sequence instead
        if _div(shape[seq_axis], dsz):
            spec[seq_axis] = "data"
    hd = shape[head_dim_axis]
    if _div(hd, msz):
        spec[head_dim_axis] = "model"
    elif _div(shape[-2], msz):  # else try kv-heads
        spec[-2] = "model"
    return P(*spec)


def state_cache_shardings(mesh: Mesh, caches):
    """Shardings for a decode-cache pytree (KV caches + SSM/xLSTM states)."""

    def one(leaf):
        shape = leaf.shape
        if len(shape) >= 5:  # (G, B, S, kv, hd) attention cache
            return NamedSharding(mesh, cache_spec(mesh, shape))
        # recurrent states: (G, B, ...) — batch over dp, biggest trailing
        # dim over model
        dsz, msz = _axis(mesh, "data"), _axis(mesh, "model")
        axes = dp_axes(mesh)
        total = int(np.prod([_axis(mesh, a) for a in axes]))
        spec = [None] * len(shape)
        if len(shape) >= 2:
            if _div(shape[1], total):
                spec[1] = axes
            elif _div(shape[1], dsz):
                spec[1] = "data"
        trail = list(range(2, len(shape)))
        if trail:
            big = max(trail, key=lambda i: shape[i])
            if _div(shape[big], msz):
                spec[big] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, caches)

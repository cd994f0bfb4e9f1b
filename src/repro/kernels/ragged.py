"""Shared ragged-stream + kernel-layout helpers.

One definition of the contract every Pallas wrapper speaks: per-channel
valid-length normalization (`vlen_vec`), post-kernel verdict masking of
ragged tails (`mask_ragged_rows`), and the lane/sublane layout padding
(`pad_layout`, `norm_block_c`, `round_up`).  `kernels/ops.py` (the TEDA
wrappers) and `detectors/ensemble.py` (the fused ensemble wrapper) both
consume these — previously each carried its own copy, and a semantics
fix in one could silently miss the other.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["default_interpret", "round_up", "norm_block_c", "vlen_vec",
           "mask_ragged_rows", "pad_layout", "TILE_ELEMS"]

# The float and Q TEDA kernels' default tile, block_t * block_c
# elements: the tiling `linerate-q` was measured at (one strip up to
# 8,192 lanes at the scheduler's block_t of 8).  The fused ensemble
# sizes its strips from its own VMEM count instead
# (`kernels/ensemble_scan.py` `max_block_c`).
TILE_ELEMS = 256 * 256


def default_interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode by default.

    Interpret mode is the CPU test path only: on the "cpu" backend the
    kernels are emulated, on "tpu" they compile with Mosaic.  Any other
    platform raises instead of silently emulating the kernels there.
    """
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels target TPU (interpret mode on CPU for "
            f"tests); no path for the {platform!r} backend")
    return platform == "cpu"


def round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def norm_block_c(block_c, block_t: int, c: int, lane_pad: int,
                 max_lanes=None) -> int:
    """Normalize the channel-block width to a static int (0 = one strip).

    `None` picks the default: one strip when the lane-padded width fits
    within `max_lanes` lanes (the kernel's widest strip at this
    `block_t`; None: the TEDA kernels' `TILE_ELEMS // block_t`), else
    the widest strip (a multiple of 128 dividing the padded width) that
    does.
    """
    if block_c is None:
        cp = round_up(c, lane_pad)
        cap = TILE_ELEMS // block_t if max_lanes is None else max_lanes
        if cp <= cap:
            return 0
        bc = max(128, cap // 128 * 128)
        while bc > 128 and cp % bc:
            bc -= 128
        return bc
    bc = int(block_c)
    if bc and bc % 128 != 0:
        raise ValueError(f"block_c must be a multiple of 128, got {bc}")
    return bc


def vlen_vec(valid_lens, t_len: int, c: int, dtype):
    """Normalize `valid_lens` to a per-channel (C,) vector.

    Returns (vlen, ragged): `ragged` is the *static* flag that the
    caller asked for a valid-length restriction at all (None means the
    whole chunk is valid for every channel — the uniform fast case that
    skips the ragged verdict masking).  Values are clamped to [0, T]:
    the kernels freeze each carry at the padded time extent, so an
    unclamped vlen would make the returned k disagree with the state
    the carries actually hold (and traced callers skip the engine's
    host-side bounds check).
    """
    if valid_lens is None:
        return jnp.full((c,), t_len, dtype), False
    vl = jnp.clip(jnp.asarray(valid_lens, dtype), 0, t_len)
    vl = vl.reshape(-1) if vl.ndim else vl
    return jnp.broadcast_to(vl, (c,)), True


def mask_ragged_rows(outlier, vlen, t_len: int):
    """No verdicts beyond a channel's valid length (eq (6) gate)."""
    rows = jnp.arange(t_len, dtype=vlen.dtype)[:, None]
    return jnp.logical_and(outlier, rows < vlen[None, :])


def pad_layout(x, rows, block_t, lane_pad, block_c=0):
    """Shared kernel-layout padding: time to block_t, lanes to lane_pad
    and (when channel-blocking) to a block_c multiple.

    `rows` are per-channel (C,) carry vectors, returned as padded (1, C')
    rows.  Returns (padded x, padded rows, un-pad slice).  Every wrapper
    routes through this so the layout contract has one definition; the
    valid length is passed to the kernel, which masks the padded tail.
    """
    t_len, c = x.shape
    tp = round_up(max(t_len, block_t), block_t)
    cp = round_up(c, lane_pad)
    if block_c:
        cp = round_up(cp, block_c)
    xp = jnp.pad(x, ((0, tp - t_len), (0, cp - c)))
    rp = tuple(jnp.pad(r.reshape(1, c), ((0, 0), (0, cp - c)))
               for r in rows)
    return xp, rp, (slice(0, t_len), slice(0, c))

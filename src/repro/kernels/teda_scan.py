"""Pallas TPU kernel: chunked-scan TEDA over multichannel streams.

TPU-native analog of the paper's FPGA pipeline (Fig. 1). The grid is
2-D `(channel-block, time-block)`: the minor (time) axis walks
time-chunks sequentially — the Mosaic pipeline overlaps the HBM->VMEM
DMA of chunk i+1 with compute on chunk i, which is exactly the role of
the FPGA's inter-module pipeline registers — while the major axis tiles
the channel lanes into independent `block_c`-wide strips.  Channels
never exchange data, so the channel-block dimension is declared
`parallel`: on a multi-core TPU Mosaic splits the strips across cores
and a wide-C engine scales past a single core instead of serializing
the whole lane extent through one.  Within a chunk, log-depth
Hillis-Steele doubling scans run over the sublane (time) axis,
vectorized across the 128-lane channel axis, so every VPU "cycle"
retires 8x128 samples instead of the FPGA's 1.

Layout contract (enforced by ops.py):
  x: (T, C) with T % block_t == 0, C % block_c == 0,
  block_t % 8 == 0, block_c % 128 == 0.
Carried state (running sum, running variance per channel) lives in VMEM
scratch — one (1, block_c) row per channel strip, re-initialized when
the time axis restarts at the next strip.  `m` arrives as an SMEM
scalar; the per-channel iteration offset `k0` and the per-channel valid
length `vlen` arrive as (1, C) carry rows tiled per strip, so every
channel may sit at a different stream position *and* retire a different
number of samples in one call (ragged multi-tenant slots; a uniform
chunk is just a broadcast vlen).  Rows of channel c at global index >=
vlen[c] are masked in-kernel (sum += 0; variance map = identity), so
the final carries — always emitted as (1, C) outputs, written once at
each strip's last time block — hold each channel's state after exactly
vlen[c] valid samples regardless of time padding.

Donation contract (`input_output_aliases`, wired by ops.py): the
k/sum/var carry-row inputs alias the final-state outputs (`k0` -> the
in-kernel final-k row, `init_sum` -> final sum, `init_var` -> final
var), and the (T, C) sample buffer `x` aliases the first (T, C) output
when dtypes agree — the stream buffer is consumed by the call, so the
kernel's HBM working set is the outputs alone.  Aliasing the carries is
safe because they are only *read* at each strip's first time block and
only *written* at its last; `vlen` is read by every grid step and has
no output successor, so it is the one carry row that stays read-only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["teda_scan_kernel", "teda_pallas_call", "row_index"]


def row_index(n: int) -> jnp.ndarray:
    """(n, 1) float32 sublane index 0..n-1.

    Mosaic's iota yields integers only, so the index is built as int32
    and cast; the values are small integers, exact in float32.
    """
    return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0).astype(
        jnp.float32)


def _shift_down(v: jnp.ndarray, d: int, fill: float) -> jnp.ndarray:
    """Rows r >= d get v[r-d]; rows < d get `fill`. Static d."""
    bt, c = v.shape
    pad = jnp.full((d, c), fill, v.dtype)
    return jnp.concatenate([pad, v[: bt - d]], axis=0)


def _cumsum_rows(v: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum over axis 0 via doubling (log2(bt) steps)."""
    bt = v.shape[0]
    d = 1
    while d < bt:
        v = v + _shift_down(v, d, 0.0)
        d *= 2
    return v


def _affine_scan_rows(a: jnp.ndarray, b: jnp.ndarray):
    """Inclusive composition scan of row-wise affine maps v -> a*v + b.

    Returns (A, B) with y_r = A_r * y_0 + B_r solving the recurrence
    y_r = a_r y_{r-1} + b_r. Doubling with identity fill (1, 0).
    """
    bt = a.shape[0]
    d = 1
    while d < bt:
        a_sh = _shift_down(a, d, 1.0)
        b_sh = _shift_down(b, d, 0.0)
        # newer map (a, b) applied after older shifted map (a_sh, b_sh)
        a, b = a * a_sh, a * b_sh + b
        d *= 2
    return a, b


def teda_scan_kernel(scal_ref, x_ref, vlen_ref, init_k_ref, init_sum_ref,
                     init_var_ref, *out_refs, block_t: int,
                     verdict_only: bool = False):
    if verdict_only:
        # slim outputs: (ecc, outlier, final k/sum/var) — HBM write
        # traffic drops from 16B to ~5B per sample (see EXPERIMENTS §Perf)
        ecc_ref, outlier_ref, fk_ref, fsum_ref, fvar_ref = out_refs[:5]
        sum_carry, var_carry = out_refs[5:]
        mean_ref = var_ref = None
    else:
        (mean_ref, var_ref, ecc_ref, outlier_ref, fk_ref, fsum_ref,
         fvar_ref) = out_refs[:7]
        sum_carry, var_carry = out_refs[7:]
    i = pl.program_id(1)  # time block (sequential, carry-chained)

    # a new channel strip restarts the time sweep: re-seed its carries
    @pl.when(i == 0)
    def _init():
        sum_carry[...] = init_sum_ref[...].astype(jnp.float32)
        var_carry[...] = init_var_ref[...].astype(jnp.float32)

    m = scal_ref[0]

    x = x_ref[...].astype(jnp.float32)  # (bt, block_c)
    bt, c = x.shape
    k0 = init_k_ref[...].astype(jnp.float32)  # (1, bc) per-channel offset
    vlen = vlen_ref[...].astype(jnp.float32)  # (1, bc) per-channel length
    g = i * block_t + row_index(bt)   # global row index, (bt, 1)
    valid = g < vlen                  # ragged-tail mask, (bt, bc)
    k = k0 + g + 1.0                  # per-channel iteration index, (bt, bc)

    # ---- MEAN module: eq (2) as a prefix sum ---------------------------
    # Invalid rows contribute nothing, so each channel's running sum
    # freezes at its last valid sample and the final carry is exact for
    # every ragged vlen vector.
    s = _cumsum_rows(jnp.where(valid, x, 0.0)) + sum_carry[...]
    mean = s / k

    # ---- VARIANCE module: eq (3) as an affine scan ---------------------
    d2 = (x - mean) ** 2
    first = k <= 1.0
    d2 = jnp.where(jnp.logical_or(first, ~valid), 0.0, d2)
    a = jnp.broadcast_to(jnp.where(first, 0.0, (k - 1.0) / k), (bt, c))
    a = jnp.where(valid, a, 1.0)  # identity map on padded rows
    b = d2 / k
    av, bv = _affine_scan_rows(a, b)
    var = av * var_carry[...] + bv

    # ---- ECCENTRICITY + OUTLIER modules: eqs (1), (5), (6) -------------
    safe = var > 0.0
    ecc = 1.0 / k + jnp.where(safe, d2 / (k * jnp.where(safe, var, 1.0)), 0.0)
    zeta = ecc * 0.5
    thr = (m * m + 1.0) / (2.0 * k)
    outlier = jnp.logical_and(zeta > thr, k >= 2.0)

    if verdict_only:
        ecc_ref[...] = ecc
        outlier_ref[...] = outlier.astype(jnp.int8)
    else:
        mean_ref[...] = mean
        var_ref[...] = var
        ecc_ref[...] = ecc
        outlier_ref[...] = outlier.astype(jnp.int32)

    sum_carry[...] = s[block_t - 1:block_t]
    var_carry[...] = var[block_t - 1:block_t]

    # final-state rows are written once, at the strip's last time block —
    # required for the carry-row donation (init rows are read at i == 0,
    # their aliased buffers overwritten only here), and one (1, C) HBM
    # write per strip instead of one per block
    @pl.when(i == pl.num_programs(1) - 1)
    def _fin():
        fk_ref[...] = k0 + vlen  # vlen pre-clamped to [0, T] by ops.py
        fsum_ref[...] = sum_carry[...]
        fvar_ref[...] = var_carry[...]


def teda_pallas_call(x: jnp.ndarray, scal: jnp.ndarray, vlen: jnp.ndarray,
                     init_k: jnp.ndarray, init_sum: jnp.ndarray,
                     init_var: jnp.ndarray, *, block_t: int,
                     block_c: int = 0, interpret: bool,
                     verdict_only: bool = False, donate: bool = True):
    """Raw pallas_call. x (T, C) pre-padded; scal = [m] f32 (1,);
    vlen / init_k / init_sum / init_var are (1, C) per-channel carry
    rows — vlen[c] is the number of leading rows of channel c that are
    valid (0..T; a uniform chunk passes a broadcast T, already clamped
    to [0, T]).  `block_c` tiles the channel axis into independent grid
    strips (0 means one strip spanning all C lanes — the 1-D grid).

    Returns (mean, var, ecc, outlier, fk, fsum, fvar) or, with
    verdict_only, (ecc, outlier, fk, fsum, fvar).  The final rows are
    always populated (each channel's state after its own vlen[c] valid
    rows; fk = k0 + vlen).  With `donate` the carry rows (and x, when
    its dtype matches the first row output) alias the outputs — callers
    must treat the operands as consumed.
    """
    t_len, c = x.shape
    if not block_c:
        block_c = c
    assert (t_len % block_t == 0 and block_t % 8 == 0
            and c % block_c == 0 and block_c % 128 == 0), (
        "ops.py must pad: T % block_t == 0, block_t % 8 == 0, "
        "C % block_c == 0, block_c % 128 == 0")
    grid = (c // block_c, t_len // block_t)

    row_spec = pl.BlockSpec((block_t, block_c), lambda j, i: (i, j),
                            memory_space=pltpu.VMEM)
    carry_spec = pl.BlockSpec((1, block_c), lambda j, i: (0, j),
                              memory_space=pltpu.VMEM)
    f32 = jnp.float32
    final_shape = [
        jax.ShapeDtypeStruct((1, c), f32),  # final k (= k0 + vlen)
        jax.ShapeDtypeStruct((1, c), f32),  # final sum
        jax.ShapeDtypeStruct((1, c), f32),  # final var
    ]
    if verdict_only:
        out_shape = [
            jax.ShapeDtypeStruct((t_len, c), f32),      # ecc
            jax.ShapeDtypeStruct((t_len, c), jnp.int8),  # outlier
        ] + final_shape
        out_specs = [row_spec, row_spec, carry_spec, carry_spec,
                     carry_spec]
    else:
        out_shape = [
            jax.ShapeDtypeStruct((t_len, c), f32),        # mean
            jax.ShapeDtypeStruct((t_len, c), f32),        # var
            jax.ShapeDtypeStruct((t_len, c), f32),        # ecc
            jax.ShapeDtypeStruct((t_len, c), jnp.int32),  # outlier
        ] + final_shape
        out_specs = [row_spec, row_spec, row_spec, row_spec,
                     carry_spec, carry_spec, carry_spec]
    n_rows = 2 if verdict_only else 4
    aliases = {}
    if donate:
        # carry-row donation: k0 -> fk, init_sum -> fsum, init_var ->
        # fvar (inputs 3/4/5; vlen is read by every step — not donated)
        aliases = {3: n_rows, 4: n_rows + 1, 5: n_rows + 2}
        if x.dtype == out_shape[0].dtype:
            aliases[1] = 0  # the stream buffer is consumed by the call
    kernel = functools.partial(teda_scan_kernel, block_t=block_t,
                               verdict_only=verdict_only)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            # channel strips are independent (multi-core scaling); the
            # time axis is the sequential carry chain
            dimension_semantics=("parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        name="teda_scan",  # the custom call's name in a device trace
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scal (1,)
            row_spec,  # x
            carry_spec,  # vlen
            carry_spec,  # init_k
            carry_spec,  # init_sum
            carry_spec,  # init_var
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((1, block_c), f32),  # running sum carry
            pltpu.VMEM((1, block_c), f32),  # running var carry
        ],
        input_output_aliases=aliases,
        compiler_params=compiler_params,
        interpret=interpret,
    )(scal, x, vlen, init_k, init_sum, init_var)

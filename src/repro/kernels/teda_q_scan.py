"""Pallas TPU kernel: chunked *integer* Q-format TEDA scan.

The quantized datapath is not associative — truncation/saturation error
depends on operation order — so the float kernel's prefix-sum tricks
would change the bits.  Instead this kernel is the direct TPU analog of
the FPGA pipeline: a sequential row loop inside each time-chunk (one
sample retired per "cycle", exactly like the paper's critical path),
vectorized across the 128-lane channel axis.  The grid is 2-D
`(channel-block, time-block)`: the minor (time) axis walks time-chunks
sequentially — Mosaic overlaps the HBM->VMEM DMA of chunk i+1 with
compute on chunk i, the inter-module pipeline registers' role — while
the major axis tiles the channel lanes into independent `block_c`-wide
strips declared `parallel`, so a wide-C engine splits across TPU cores
instead of serializing every lane through one.

Inside a block the datapath is *rescheduled* around the bit-serial
dividers (the FPGA's multi-cycle units, ~WL iterations each).  Only
the MEAN and VARIANCE recurrences are genuinely sequential, and both
are a saturating multiply-add once their divider terms exist; every
divider input is either counter-only (rk=(k-1)/k, 1/k, (m^2+1)/2k),
depends only on the samples (x/k), or is a pure per-row function of
values the recurrences produce (d2/k, d2/var, ratio/k).  So the kernel
runs two sequential register loops — one bare saturating multiply-add
per sample each, the MEAN and VARIANCE accumulator registers, with the
k=1 overrides folded into the hoisted terms (rk = 0 and x/1 = x at
k=1) — and executes every divider as one vectorized whole-block pass
outside them: bit-identical values (the dividers are elementwise; each
element sees exactly the inputs and operation order of
`repro.fixedpoint.teda_q._q_step_u`, the function `teda_q_scan_chan`
scans over — the oracle this kernel is tested bit-exact against, for
every `block_c`, since channels never exchange data).  The sequential
critical path drops from four bit-serial divisions per sample to none,
and each hoisted pass runs through the host-width exact image of the
divider (`kernels/qdiv.py`): one integer divide plus FL restoring
steps instead of 31+FL shift-subtract iterations, same bits.

Layout contract (enforced by ops.py):
  x: (T, C) int32 Q-values, T % block_t == 0, C % block_c == 0,
  block_t % 8 == 0, block_c % 128 == 0.  SMEM scalar: [msq1_q] int32.
  The per-channel counter offset `k0` and the per-channel valid length
  `vlen` are (1, C) int32 carry rows (slots may sit at different stream
  positions and retire different sample counts in one call; a uniform
  chunk is a broadcast vlen).  Rows of channel c at global index >=
  vlen[c] are masked: that channel's mean/var carries freeze, so the
  final-state rows — always emitted as (1, C) outputs, written once at
  each strip's last time block — are exact for every ragged vlen
  vector, bit-for-bit with a per-channel isolated run.

Donation contract (wired by ops.py): `k0` aliases the in-kernel
final-k output, `init_mean`/`init_var` alias the final mean/var rows,
and the (T, C) Q-sample buffer `x` aliases the first (T, C) output —
the call consumes its operands and allocates no fresh HBM for them.
`vlen` is read by every grid step (the ragged mask) and has no output
successor, so it is the one carry row left undonated.

`verdict_only` drops the per-row mean/var outputs: the serving engine
consumes only (ecc, outlier) + the final carries, and skipping two
(T, C) int32 VMEM->HBM streams is a measured ~1.2x on the Q hot path
(the matching wrapper-level win — not re-deriving the (T, C) bit-serial
threshold the engine never reads — is in ops.teda_q_scan_verdict).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.fixedpoint.qformat import QFormat, sat_add, sat_mul, sat_sub
from repro.kernels.qdiv import fast_div_qi, fast_div_qq

__all__ = ["teda_q_scan_kernel", "teda_q_pallas_call"]


def teda_q_scan_kernel(scal_ref, x_ref, vlen_ref, init_k_ref,
                       init_mean_ref, init_var_ref, *out_refs,
                       block_t: int, fmt: QFormat,
                       verdict_only: bool = False):
    if verdict_only:
        ecc_ref, outlier_ref, fk_ref, fmean_ref, fvar_ref = out_refs[:5]
        scratch = out_refs[5:]
        mean_ref = var_ref = None
    else:
        (mean_ref, var_ref, ecc_ref, outlier_ref, fk_ref, fmean_ref,
         fvar_ref) = out_refs[:7]
        scratch = out_refs[7:]
    mean_carry, var_carry, mean_scr, var_scr, rk_scr, term_scr = scratch
    i = pl.program_id(1)  # time block (sequential, carry-chained)

    # a new channel strip restarts the time sweep: re-seed its carries
    @pl.when(i == 0)
    def _init():
        mean_carry[...] = init_mean_ref[...]
        var_carry[...] = init_var_ref[...]

    msq1 = scal_ref[0]
    vlen = vlen_ref[...]  # (1, bc) int32 per-channel valid length
    k0 = init_k_ref[...]  # (1, bc) int32 per-channel counter offset
    xb = x_ref[...]       # (block_t, bc) int32 Q samples

    # the FPGA's counter register for every row of the block
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (block_t, 1), 0)
    kv = k0 + i * block_t + 1 + row_iota     # (block_t, bc)
    first_b = kv <= 1

    # every data-independent divider, vectorized over the whole block:
    # the counter-only triple (rk = (k-1)/k, 1/k, thr = (m^2+1)/2k) of
    # `_q_counter_terms` and the MEAN module's x/k (eq (2)) — computed
    # through the host-width image of the bit-serial divider
    # (kernels/qdiv.py), one whole-block pass each instead of one
    # 31+FL-step division per row
    rk_b = fast_div_qq(fmt, kv - 1, kv)
    inv_b = fast_div_qi(fmt, jnp.broadcast_to(jnp.int32(fmt.one),
                                              kv.shape), kv)
    thr_b = fast_div_qi(fmt, jnp.broadcast_to(jnp.asarray(msq1,
                                                          jnp.int32),
                                              kv.shape), 2 * kv)
    # the row loops read their per-row terms back through VMEM refs:
    # Mosaic lowers a dynamic row slice of a ref, not of a value
    rk_scr[...] = rk_b
    term_scr[...] = fast_div_qi(fmt, xb, kv)   # x/k, the MEAN term

    def row_valid(r):
        return i * block_t + r < vlen  # (1, bc) ragged mask of row r

    # MEAN recurrence, eq (2): mu = rk * mu + x/k — a bare saturating
    # multiply-add per row, the MEAN module's accumulator register.  The
    # k=1 override of `_q_mean_update` is bit-redundant here: at k=1,
    # rk = div_qq(0, 1) = 0 and x/k = div_qi(x, 1) = x exactly (division
    # by one is exact in the restoring divider, and x is in-format), so
    # the multiply-add itself yields x.
    def mean_row(r, mean):
        mean_n = sat_add(fmt, sat_mul(fmt, rk_scr[pl.ds(r, 1), :], mean),
                         term_scr[pl.ds(r, 1), :])
        mean_scr[pl.ds(r, 1), :] = mean_n
        # each channel's ragged tail must not advance its carried state
        return jnp.where(row_valid(r), mean_n, mean)

    mean_carry[...] = jax.lax.fori_loop(
        0, block_t, mean_row, mean_carry[...])

    # VARIANCE divider d2/k of eq (3): d2 = (x - mu_k)^2 is elementwise
    # in the banked mean rows, so it — and its divider — leave the
    # sequential path too.  The k=1 override (var resets to 0) is folded
    # in by zeroing the divider term: rk = 0 at k=1 makes the
    # multiply-add produce exactly 0.
    mean_b = mean_scr[...]
    d_b = sat_sub(fmt, xb, mean_b)
    d2_b = sat_mul(fmt, d_b, d_b)
    term_scr[...] = jnp.where(first_b, 0, fast_div_qi(fmt, d2_b, kv))
    if not verdict_only:
        mean_ref[...] = mean_b

    # VARIANCE recurrence: var = rk * var + d2/k — the second
    # accumulator register, again a bare multiply-add per row
    def var_row(r, var):
        var_n = sat_add(fmt, sat_mul(fmt, rk_scr[pl.ds(r, 1), :], var),
                        term_scr[pl.ds(r, 1), :])
        var_scr[pl.ds(r, 1), :] = var_n
        return jnp.where(row_valid(r), var_n, var)

    var_carry[...] = jax.lax.fori_loop(0, block_t, var_row, var_carry[...])

    # ECCENTRICITY + OUTLIER, eqs (1)(5)(6): pure per-row functions of
    # the banked (d2, var) rows — the d2/var and ratio/k dividers run as
    # single whole-block passes, bit-identical to `_q_post_d2` (the ops
    # are elementwise; each element sees the same inputs in the same
    # order).  The var>0 guard also covers first rows (var == 0 there).
    var_b = var_scr[...]
    safe = var_b > 0
    ratio = fast_div_qq(fmt, d2_b, jnp.where(safe, var_b, 1))
    ecc = sat_add(fmt, inv_b,
                  jnp.where(safe, fast_div_qi(fmt, ratio, kv), 0))
    ecc_ref[...] = ecc
    outlier_ref[...] = (((ecc >> 1) > thr_b) & (kv >= 2)).astype(jnp.int8)
    if not verdict_only:
        var_ref[...] = var_b

    # final-state rows written once, at the strip's last time block —
    # required for the carry-row donation (init rows are read at i == 0,
    # their aliased buffers overwritten only here), and one (1, C) HBM
    # write per strip instead of one per block
    @pl.when(i == pl.num_programs(1) - 1)
    def _fin():
        fk_ref[...] = k0 + vlen  # vlen pre-clamped to [0, T] by ops.py
        fmean_ref[...] = mean_carry[...]
        fvar_ref[...] = var_carry[...]


def teda_q_pallas_call(x: jnp.ndarray, scal: jnp.ndarray,
                       vlen: jnp.ndarray, init_k: jnp.ndarray,
                       init_mean: jnp.ndarray, init_var: jnp.ndarray, *,
                       fmt: QFormat, block_t: int, block_c: int = 0,
                       interpret: bool, verdict_only: bool = False,
                       donate: bool = True):
    """Raw pallas_call. x (T, C) int32 pre-padded; scal = [msq1] (1,);
    vlen / init_k / init_mean / init_var are (1, C) int32 carry rows —
    vlen[c] is the number of leading valid rows of channel c (0..T,
    already clamped).  `block_c` tiles the channel axis into independent
    grid strips (0 means one strip spanning all C lanes — the 1-D grid).

    Returns (mean, var, ecc, outlier, fk, final_mean, final_var) or,
    with verdict_only, (ecc, outlier, fk, final_mean, final_var); the
    final rows are always populated (each channel's state after its own
    vlen[c] valid rows; fk = k0 + vlen).  With `donate` the carry rows
    and x alias the outputs — callers must treat the operands as
    consumed.
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
    i32 = jnp.int32
    final_shape = [
        jax.ShapeDtypeStruct((1, c), i32),  # final k
        jax.ShapeDtypeStruct((1, c), i32),  # final mean (Q)
        jax.ShapeDtypeStruct((1, c), i32),  # final var (Q)
    ]
    if verdict_only:
        out_shape = [
            jax.ShapeDtypeStruct((t_len, c), i32),       # ecc (Q)
            jax.ShapeDtypeStruct((t_len, c), jnp.int8),  # outlier flag
        ] + final_shape
        out_specs = [row_spec, row_spec, carry_spec, carry_spec,
                     carry_spec]
    else:
        out_shape = [
            jax.ShapeDtypeStruct((t_len, c), i32),       # mean (Q)
            jax.ShapeDtypeStruct((t_len, c), i32),       # var (Q)
            jax.ShapeDtypeStruct((t_len, c), i32),       # ecc (Q)
            jax.ShapeDtypeStruct((t_len, c), jnp.int8),  # outlier flag
        ] + final_shape
        out_specs = [row_spec, row_spec, row_spec, row_spec,
                     carry_spec, carry_spec, carry_spec]
    n_rows = 2 if verdict_only else 4
    aliases = {}
    if donate:
        # k0 -> fk, init_mean -> fmean, init_var -> fvar; the consumed
        # Q-sample buffer aliases the first (T, C) int32 output (vlen is
        # read by every step — not donated)
        aliases = {1: 0, 3: n_rows, 4: n_rows + 1, 5: n_rows + 2}
    kernel = functools.partial(teda_q_scan_kernel, block_t=block_t,
                               fmt=fmt, verdict_only=verdict_only)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            # channel strips are independent (multi-core scaling); the
            # time axis is the sequential carry chain
            dimension_semantics=("parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # scal (1,) int32
            row_spec,    # x
            carry_spec,  # vlen
            carry_spec,  # init_k
            carry_spec,  # init_mean
            carry_spec,  # init_var
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        scratch_shapes=[
            pltpu.VMEM((1, block_c), i32),        # running mean carry
            pltpu.VMEM((1, block_c), i32),        # running var carry
            pltpu.VMEM((block_t, block_c), i32),  # banked mean rows
            pltpu.VMEM((block_t, block_c), i32),  # banked var rows
            pltpu.VMEM((block_t, block_c), i32),  # rk = (k-1)/k rows
            pltpu.VMEM((block_t, block_c), i32),  # x/k, then d2/k rows
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(scal, x, vlen, init_k, init_mean, init_var)

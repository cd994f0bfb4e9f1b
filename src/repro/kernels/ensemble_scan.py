"""Fused multi-detector Pallas kernel: K detectors x C channels per call.

One (chunk_t, C) call on the PR 7 2-D `(channel-block, time-block)`
grid evaluates every detector of the ensemble (`repro.detectors`) for
every channel.  The carried state is no longer a fixed 2W+1 moment
formula: it is the `StateSpec` layout from `detectors/spec.py` — the
shared moment fabric (prefix-sum tails + the TEDA variance recursion)
in rows [0, 2W], then one opaque `(rows_k, C)` region group per
non-moment member, in detector order.  The whole block lives in ONE
`(spec.rows, block_c)` VMEM scratch tile, re-seeded from `aux` at each
strip's first time block and written back once at its last (the
carry/donation discipline of `teda_scan.py`).

Per (block_t, block_c) tile the kernel runs a per-member state-advance
dispatch:

  * moment members (teda / rde / zscore) share the masked prefix sum S
    (Hillis-Steele `_cumsum_rows`), the S2 twin, and the TEDA affine
    variance scan — the EXACT arithmetic of the PR 8 kernel, reading
    and writing the same aux rows, so moment-only ensembles are
    bit/array-identical to it (and the TEDA lane to `teda_scan.py`);
  * "hst" advances its opaque leaf-mass tables + phase row with a
    sequential per-row loop of exact small-integer f32 ops — identical
    bits to the `detectors/hst.py` oracle;
  * "teda-q" advances its opaque int32 Q registers (bitcast in the f32
    aux block) on the `teda_q_scan.py` divider-hoisted schedule through
    `kernels/qdiv.py` — bit-exact with the `detectors/teda_q.py`
    oracle, including the in-kernel f32 quantization of the m^2+1 ROM
    constant from the per-channel m carry.

Outputs per call: the (T, C) int32 detector bitmask (bit d = detector
d flagged, masked by selection weight and ragged validity), the (T, C)
weighted-vote verdict (sum_d w_d * flag_d >= thr[c], accumulated in
detector order in float32 — the exact order a host recomputation from
the emitted bitmask must use; the Q member's flag enters the same f32
accumulation, which is what makes the Q-path vote host-recomputable
bit-exactly), and K per-detector (T, C) float32 SCORE streams (TEDA
eccentricity, RDE Cauchy density, squared z-score, HST reference-cell
mass, dequantized Q eccentricity — zero on invalid rows).

Selection (`sel`, (K, C) weights; 0 = unselected) gates only flags and
the vote — state always advances for every member, which is what makes
a detector-masked slot bit-identical to a single-detector run of the
same stream.  Ragged `vlen` semantics are the TEDA kernel's: validity
is a per-channel prefix, invalid rows advance nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.detectors.spec import (HST_LEAVES, HST_RANGE, MOMENT_MEMBERS,
                                  ensemble_spec, f32_to_i32_bits,
                                  i32_to_f32_bits)
from repro.fixedpoint.qformat import sat_add, sat_mul, sat_sub
from repro.kernels.qdiv import fast_div_qi, fast_div_qq
from repro.kernels.ragged import round_up
from repro.kernels.teda_scan import (_affine_scan_rows, _cumsum_rows,
                                     row_index)

__all__ = ["ensemble_scan_kernel", "ensemble_pallas_call",
           "vmem_bytes_per_lane", "max_block_c"]

# (block_t, block_c) VMEM row banks each sequential lane reads and
# writes one row at a time (Mosaic lowers a dynamic row slice of a
# ref, not of a value): hst banks its scores and flags (f32), teda-q
# its rk / divider-term / mean / var rows (int32)
_HST_BANKS = 2
_TEDA_Q_BANKS = 4

#: Mosaic's scoped-VMEM limit on a TPU v5e ("limit 16.00M" in its
#: out-of-memory error): a call's blocks, its scratch and the values of
#: its arithmetic share it
SCOPED_VMEM_BYTES = 16 << 20


def _n_banks(detectors) -> int:
    return ((_HST_BANKS if "hst" in detectors else 0)
            + (_TEDA_Q_BANKS if "teda-q" in detectors else 0))


def vmem_bytes_per_lane(block_t: int, detectors, window: int) -> int:
    """VMEM bytes one lane of a (block_t, block_c) call holds in blocks
    and scratch.  Pallas keeps two copies of every pipelined block: the
    input x, the vlen / k0 / m / thr rows, the (K, .) selection and the
    state block in; the bitmask, the int8 vote, fk, the state block and
    the K score rows out.  One copy of the scratch: the state tile and
    the sequential lanes' row banks.  Rows count in whole tiles of 8
    sublanes (32 for int8)."""
    f32 = 4
    tt = round_up(block_t, 8)
    state = round_up(ensemble_spec(detectors, window).rows, 8)
    k = len(detectors)
    blocks = ((tt + 4 * 8 + round_up(k, 8) + state      # in
               + tt + 8 + state + k * tt) * f32         # out
              + round_up(block_t, 32))                  # the int8 vote
    return 2 * blocks + (state + _n_banks(detectors) * tt) * f32


def max_block_c(block_t: int, detectors, window: int) -> int:
    """The widest strip, in lanes, whose blocks and scratch take at most
    half the scoped VMEM; the other half holds the values of the
    tile's arithmetic, which Mosaic places beside them.  (Compiled for
    a v5e, K=5: 8,192 lanes at block_t 8 need 16.54 MiB, of which this
    count is 89%; 512 lanes at block_t 256 need 23.51 MiB, 46%.)"""
    return SCOPED_VMEM_BYTES // 2 // vmem_bytes_per_lane(
        block_t, detectors, window)


def _hst_lane(state, spec, x_ref, row_valid, m, banks, *, window: int):
    """Advance the "hst" opaque regions; returns (flags, scores).

    Sequential per-row loop (the window flip is a data-dependent state
    machine, not a scan), but every op is an exact small-integer f32
    add/compare — identical bits to the `hst_scan` oracle step.
    """
    bt = x_ref.shape[0]
    score_bank, flag_bank = banks
    ell = HST_LEAVES
    off = spec.offset("hst:ref")
    ref0 = state[off:off + ell, :]
    cur0 = state[off + ell:off + 2 * ell, :]
    ph0 = state[off + 2 * ell:off + 2 * ell + 1, :]
    lo, hi = HST_RANGE
    scale = float(ell) / (hi - lo)
    leaves = row_index(ell)
    wn = float(int(window) * ell)

    def body(r, carry):
        ref, cur, ph = carry
        x_r = x_ref[pl.ds(r, 1), :].astype(jnp.float32)  # (1, bc)
        lf_r = jnp.clip(jnp.floor((x_r - lo) * scale), 0.0,
                        float(ell - 1))
        v_r = row_valid(r)                         # (1, bc) bool
        onehot = leaves == lf_r                    # (ell, bc)
        score = jnp.sum(jnp.where(onehot, ref, 0.0), axis=0,
                        keepdims=True)
        filled = jnp.sum(ref, axis=0, keepdims=True) > 0.0
        flag = v_r & filled & (score * m < float(window))
        cur1 = cur + jnp.where(onehot & v_r, 1.0, 0.0)
        ph1 = ph + v_r.astype(jnp.float32)
        flip = ph1 == wn
        ref1 = jnp.where(flip, cur1, ref)
        cur2 = jnp.where(flip, 0.0, cur1)
        ph2 = jnp.where(flip, 0.0, ph1)
        score_bank[pl.ds(r, 1), :] = jnp.where(v_r, score, 0.0)
        flag_bank[pl.ds(r, 1), :] = flag.astype(jnp.float32)
        return ref1, cur2, ph2

    ref_f, cur_f, ph_f = jax.lax.fori_loop(0, bt, body, (ref0, cur0, ph0))
    state[off:off + ell, :] = ref_f
    state[off + ell:off + 2 * ell, :] = cur_f
    state[off + 2 * ell:off + 2 * ell + 1, :] = ph_f
    return flag_bank[...] > 0.0, score_bank[...]


def _teda_q_lane(state, spec, x, valid, row_valid, k, m, fmt, banks):
    """Advance the "teda-q" opaque Q registers; returns (flags, scores).

    The `teda_q_scan.py` kernel's rescheduled datapath on the member's
    bitcast int32 regions: every counter-only divider (rk=(k-1)/k, 1/k,
    thr=(m^2+1)/2k) and the sample divider x/k run as whole-block
    passes through the host-width exact divider image
    (`kernels/qdiv.py`); the MEAN and VARIANCE recurrences are two slim
    saturating multiply-add row loops with ragged carry freeze.
    Bit-exact with `_q_step_u` (hence the `teda_q_member_scan` oracle):
    each element sees the same inputs and operation order, with the
    k=1 overrides folded into the hoisted terms (rk = 0 and x/1 = x).
    """
    bt = x.shape[0]
    rk_bank, term_bank, mean_bank, var_bank = banks
    i32 = jnp.int32
    offm = spec.offset("teda-q:mean")
    offv = spec.offset("teda-q:var")
    mean0 = f32_to_i32_bits(state[offm:offm + 1, :])
    var0 = f32_to_i32_bits(state[offv:offv + 1, :])
    xq = fmt.quantize(x)                    # (bt, bc) int32 Q
    msq1 = fmt.quantize(m * m + 1.0)        # (1, bc) — the f32 m carry
    kv = k.astype(i32)                      # exact: k < 2^24
    first = kv <= 1

    rk_bank[...] = fast_div_qq(fmt, kv - 1, kv)
    inv_b = fast_div_qi(fmt, jnp.broadcast_to(i32(fmt.one), kv.shape), kv)
    thr_b = fast_div_qi(fmt, jnp.broadcast_to(msq1, kv.shape), 2 * kv)
    term_bank[...] = fast_div_qi(fmt, xq, kv)   # x/k, the MEAN term

    def mean_row(r, mean):
        mean_n = sat_add(fmt, sat_mul(fmt, rk_bank[pl.ds(r, 1), :], mean),
                         term_bank[pl.ds(r, 1), :])
        mean_bank[pl.ds(r, 1), :] = mean_n
        return jnp.where(row_valid(r), mean_n, mean)

    mean_f = jax.lax.fori_loop(0, bt, mean_row, mean0)

    d_b = sat_sub(fmt, xq, mean_bank[...])
    d2_b = sat_mul(fmt, d_b, d_b)
    term_bank[...] = jnp.where(first, 0, fast_div_qi(fmt, d2_b, kv))

    def var_row(r, var):
        var_n = sat_add(fmt, sat_mul(fmt, rk_bank[pl.ds(r, 1), :], var),
                        term_bank[pl.ds(r, 1), :])
        var_bank[pl.ds(r, 1), :] = var_n
        return jnp.where(row_valid(r), var_n, var)

    var_f = jax.lax.fori_loop(0, bt, var_row, var0)
    var_b = var_bank[...]

    safe = var_b > 0
    ratio = fast_div_qq(fmt, d2_b, jnp.where(safe, var_b, 1))
    ecc = sat_add(fmt, inv_b,
                  jnp.where(safe, fast_div_qi(fmt, ratio, kv), 0))
    flags = ((ecc >> 1) > thr_b) & (kv >= 2)
    scores = jnp.where(valid, fmt.dequantize(ecc), 0.0)
    state[offm:offm + 1, :] = i32_to_f32_bits(mean_f)
    state[offv:offv + 1, :] = i32_to_f32_bits(var_f)
    return flags, scores


def ensemble_scan_kernel(x_ref, vlen_ref, k0_ref, m_ref, thr_ref, sel_ref,
                         aux_ref, bits_ref, vote_ref, fk_ref, aux_out_ref,
                         *rest, block_t: int, window: int,
                         detectors: tuple, fmt=None):
    n_det = len(detectors)
    score_refs = rest[:n_det]       # K per-detector (bt, bc) f32 outputs
    state = rest[n_det]             # the (spec.rows, bc) scratch tile
    banks = list(rest[n_det + 1:])  # row banks of the sequential lanes
    spec = ensemble_spec(detectors, window)
    w = window
    moment = any(d in MOMENT_MEMBERS for d in detectors)
    need_s2 = ("rde" in detectors) or ("zscore" in detectors)
    i = pl.program_id(1)  # time block (sequential, carry-chained)

    # a new channel strip restarts the time sweep: re-seed the whole
    # spec block from aux — a raw f32 copy, so the bitcast i32 regions'
    # payloads survive untouched
    @pl.when(i == 0)
    def _init():
        state[...] = aux_ref[...]

    x = x_ref[...].astype(jnp.float32)        # (bt, bc)
    bt, c = x.shape
    k0 = k0_ref[...].astype(jnp.float32)      # (1, bc)
    vlen = vlen_ref[...].astype(jnp.float32)  # (1, bc)
    m = m_ref[...].astype(jnp.float32)        # (1, bc) per-channel m
    thr = thr_ref[...].astype(jnp.float32)    # (1, bc) vote threshold
    g = i * block_t + row_index(bt)    # global row index, (bt, 1)
    valid = g < vlen                   # ragged-tail mask, (bt, bc)
    k = k0 + g + 1.0                   # per-channel iteration index
    m2 = m * m

    flags, scores = {}, {}
    if moment:
        # ---- shared MEAN fabric: one prefix sum feeds every moment
        # member (aux rows [0, 2W] — the PR 8 arithmetic, verbatim) ----
        s = _cumsum_rows(jnp.where(valid, x, 0.0)) + state[w - 1:w, :]
        mean = s / k
        dr = (x - mean) ** 2           # raw distance to the running mean

    if "teda" in detectors:
        # eq (3) affine scan + eqs (1)/(5)/(6) — the exact arithmetic of
        # `teda_scan_kernel`, so this lane's flags are bit-identical to
        # the standalone "pallas" backend at equal block_t
        first = k <= 1.0
        d2 = jnp.where(jnp.logical_or(first, ~valid), 0.0, dr)
        a = jnp.broadcast_to(jnp.where(first, 0.0, (k - 1.0) / k), (bt, c))
        a = jnp.where(valid, a, 1.0)   # identity map on padded rows
        av, bv = _affine_scan_rows(a, d2 / k)
        var = av * state[2 * w:2 * w + 1, :] + bv
        safe = var > 0.0
        ecc = 1.0 / k + jnp.where(safe,
                                  d2 / (k * jnp.where(safe, var, 1.0)), 0.0)
        flags["teda"] = jnp.logical_and(ecc * 0.5 > (m2 + 1.0) / (2.0 * k),
                                        k >= 2.0)
        scores["teda"] = ecc
        state[2 * w:2 * w + 1, :] = var[block_t - 1:block_t]

    if need_s2:
        s2 = (_cumsum_rows(jnp.where(valid, x * x, 0.0))
              + state[2 * w - 1:2 * w, :])

    if "rde" in detectors:
        # biased variance from the running moments (Angelov's RDE)
        meanr = s / k
        varb = s2 / k - meanr * meanr
        flags["rde"] = (varb > 0.0) & (k >= 2.0) & (dr > m2 * varb)
        okr = varb > 0.0
        scores["rde"] = 1.0 / (1.0 + jnp.where(
            okr, dr / jnp.where(okr, varb, 1.0), 0.0))

    if "zscore" in detectors:
        # windowed moments as prefix-sum differences against the W-deep
        # carried tails: s_full[p] = S_{k_blockstart + p - W + 1}, so the
        # lag row S_{k - W} of in-block row r is s_full[r]
        s_full = jnp.concatenate([state[0:w, :], s], axis=0)  # (W+bt, c)
        s2_full = jnp.concatenate([state[w:2 * w, :], s2], axis=0)
        winsum = s - s_full[:bt]
        winsq = s2 - s2_full[:bt]
        n = jnp.minimum(k, float(w))
        muw = winsum / n
        sigw = winsq / n - muw * muw
        dz = (x - muw) ** 2
        flags["zscore"] = (sigw > 0.0) & (k >= 2.0) & (dz > m2 * sigw)
        okz = sigw > 0.0
        scores["zscore"] = jnp.where(okz, dz / jnp.where(okz, sigw, 1.0),
                                     0.0)
        # advance the tails to the valid extent of this block: new tail
        # row j is s_full[n_valid + j] (validity is a prefix, so the
        # tail stays contiguous for every ragged vlen).  Static-W loop
        # of 2-D masked reductions — one per tail row — instead of a
        # 3-D gather (sublane-dynamic indexing is not a Mosaic op).
        n_valid = jnp.clip(vlen - i * block_t, 0.0, float(bt))  # (1, c)
        rows = row_index(bt + w)
        new_s, new_s2 = [], []
        for j in range(w):
            hit = rows == (n_valid + float(j))  # (bt+w, c), exact f32
            new_s.append(jnp.sum(jnp.where(hit, s_full, 0.0), axis=0,
                                 keepdims=True))
            new_s2.append(jnp.sum(jnp.where(hit, s2_full, 0.0), axis=0,
                                  keepdims=True))
        state[0:w, :] = jnp.concatenate(new_s, axis=0)
        state[w:2 * w, :] = jnp.concatenate(new_s2, axis=0)
    elif moment:
        state[w - 1:w, :] = s[block_t - 1:block_t]
        if need_s2:
            state[2 * w - 1:2 * w, :] = s2[block_t - 1:block_t]

    # ---- opaque-region members: per-member state-advance dispatch -----
    def row_valid(r):  # (1, bc) ragged mask of in-block row r
        return (i * block_t + r).astype(jnp.float32) < vlen

    if "hst" in detectors:
        hst_banks, banks = banks[:_HST_BANKS], banks[_HST_BANKS:]
        flags["hst"], scores["hst"] = _hst_lane(
            state, spec, x_ref, row_valid, m, hst_banks, window=window)
    if "teda-q" in detectors:
        flags["teda-q"], scores["teda-q"] = _teda_q_lane(
            state, spec, x, valid, row_valid, k, m, fmt, banks)

    # ---- selection-masked bitmask + weighted vote + score streams -----
    bits = jnp.zeros((bt, c), jnp.int32)
    votew = jnp.zeros((bt, c), jnp.float32)
    totw = jnp.zeros((1, c), jnp.float32)
    for d, name in enumerate(detectors):
        wrow = sel_ref[d:d + 1, :].astype(jnp.float32)  # (1, bc)
        f = flags[name] & (wrow > 0.0) & valid
        bits = bits + f.astype(jnp.int32) * (1 << d)
        votew = votew + f.astype(jnp.float32) * wrow
        totw = totw + wrow
        score_refs[d][...] = jnp.where(valid, scores[name], 0.0)
    vote = (votew >= thr) & (totw > 0.0) & valid
    bits_ref[...] = bits
    vote_ref[...] = vote.astype(jnp.int8)

    # final carries once per strip, at its last time block (the aux/k0
    # donation discipline of `teda_scan.py`)
    @pl.when(i == pl.num_programs(1) - 1)
    def _fin():
        fk_ref[...] = k0 + vlen  # vlen pre-clamped to [0, T] by wrapper
        aux_out_ref[...] = state[...]


def ensemble_pallas_call(x: jnp.ndarray, vlen: jnp.ndarray,
                         k0: jnp.ndarray, m: jnp.ndarray,
                         thr: jnp.ndarray, sel: jnp.ndarray,
                         aux: jnp.ndarray, *, block_t: int,
                         block_c: int = 0, window: int,
                         detectors: tuple, fmt=None, interpret: bool,
                         donate: bool = True):
    """Raw pallas_call.  x (T, C) pre-padded; vlen / k0 / m / thr are
    (1, C) per-channel carry rows; sel is the (K, C) selection-weight
    block; aux the (spec.rows, C) packed state block of
    `ensemble_spec(detectors, window)`.  `detectors` is the static
    ensemble tuple — bit d of the emitted mask is detectors[d]; `fmt`
    is the QFormat of the "teda-q" member (required iff present).
    Returns (det_bits, vote, fk, aux_final, score_0, ..., score_{K-1})
    with one (T, C) f32 score stream per detector.  With `donate`, k0
    aliases fk and aux aliases aux_final — callers must treat those
    operands as consumed.
    """
    t_len, c = x.shape
    if not block_c:
        block_c = c
    spec = ensemble_spec(detectors, window)
    n_aux = spec.rows
    assert (t_len % block_t == 0 and block_t % 8 == 0
            and c % block_c == 0 and block_c % 128 == 0), (
        "wrapper must pad: T % block_t == 0, block_t % 8 == 0, "
        "C % block_c == 0, block_c % 128 == 0")
    assert aux.shape == (n_aux, c) and sel.shape == (len(detectors), c)
    if "teda-q" in detectors and fmt is None:
        raise ValueError("the teda-q member needs fmt=QFormat(...)")
    grid = (c // block_c, t_len // block_t)

    row_spec = pl.BlockSpec((block_t, block_c), lambda j, i: (i, j),
                            memory_space=pltpu.VMEM)
    carry_spec = pl.BlockSpec((1, block_c), lambda j, i: (0, j),
                              memory_space=pltpu.VMEM)
    sel_spec = pl.BlockSpec((len(detectors), block_c), lambda j, i: (0, j),
                            memory_space=pltpu.VMEM)
    aux_spec = pl.BlockSpec((n_aux, block_c), lambda j, i: (0, j),
                            memory_space=pltpu.VMEM)
    f32 = jnp.float32
    banks = ([pltpu.VMEM((block_t, block_c), f32)] * _HST_BANKS
             if "hst" in detectors else [])
    if "teda-q" in detectors:
        banks += [pltpu.VMEM((block_t, block_c), jnp.int32)] * _TEDA_Q_BANKS
    out_shape = [
        jax.ShapeDtypeStruct((t_len, c), jnp.int32),  # detector bitmask
        jax.ShapeDtypeStruct((t_len, c), jnp.int8),   # fused vote
        jax.ShapeDtypeStruct((1, c), f32),            # final k
        jax.ShapeDtypeStruct((n_aux, c), f32),        # final aux block
    ] + [jax.ShapeDtypeStruct((t_len, c), f32)        # per-member score
         for _ in detectors]
    out_specs = [row_spec, row_spec, carry_spec, aux_spec] + \
                [row_spec for _ in detectors]
    aliases = {}
    if donate:
        # k0 -> fk, aux -> final aux (inputs 2 / 6); vlen, m, thr and
        # sel are read by every grid step — never donated
        aliases = {2: 2, 6: 3}
    kernel = functools.partial(ensemble_scan_kernel, block_t=block_t,
                               window=window, detectors=tuple(detectors),
                               fmt=fmt)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    # no `name=`: a device trace names this custom call after its jitted
    # wrapper (`%_padded_ensemble_call`), which the benchmark's byte
    # model of the kernel matches; a name would replace it
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_spec, carry_spec, carry_spec, carry_spec,
                  carry_spec, sel_spec, aux_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((n_aux, block_c), f32),  # the packed StateSpec
        ] + banks,
        input_output_aliases=aliases,
        compiler_params=compiler_params,
        interpret=interpret,
    )(x, vlen, k0, m, thr, sel, aux)

"""Multi-device TEDA: one logical stream scanned across a mesh axis.

This is the multi-pod generalization of TEDAClassBDp (block-parallel TEDA,
ref [15] of the paper): the time axis is sharded over a mesh axis, each
device runs the parallel scan of `core/scan.py` on its local block, and
tiny O(N) carries are exchanged with `all_gather` so that every device
fixes its block up to the *global* prefix statistics. Three collectives of
size O(devices * N) total — independent of T.

Usable standalone (monitor streams recorded across thousands of steps,
re-scored in one sharded pass) and as the scalable data-screening stage of
the input pipeline.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.teda import TedaOutput, TedaState, teda_threshold

__all__ = ["distributed_teda", "make_distributed_teda"]


def _local_shard_scan(x: jnp.ndarray, m, axis_name: str
                      ) -> Tuple[TedaState, TedaOutput]:
    """Body run per-device under shard_map. x: (T_local, N)."""
    t_local = x.shape[0]
    idx = jax.lax.axis_index(axis_name)
    x = x.astype(jnp.float32)

    # ---- pass 1: exclusive prefix of running sums -----------------------
    local_sum = jnp.sum(x, axis=0)  # (N,)
    all_sums = jax.lax.all_gather(local_sum, axis_name)  # (D, N)
    # static device count from the gathered shape (jax.lax.axis_size is
    # not available on older JAX)
    ndev = all_sums.shape[0]
    prefix_mask = (jnp.arange(ndev) < idx).astype(x.dtype)  # exclusive
    s_prev = jnp.einsum("d,dn->n", prefix_mask, all_sums)
    k_prev = idx * t_local  # static per-device sample offset

    # ---- local mean / distance terms with global k -----------------------
    k = (k_prev + jnp.arange(1, t_local + 1)).astype(x.dtype)  # (T_local,)
    s = s_prev[None] + jnp.cumsum(x, axis=0)
    mean = s / k[:, None]
    d2 = jnp.sum((x - mean) ** 2, axis=-1)
    first_row = k <= 1.0
    d2 = jnp.where(first_row, 0.0, d2)

    # ---- pass 2: exclusive prefix of the variance affine maps -----------
    # var_k = a_k var_{k-1} + b_k. Across a block the composed map is
    # (A, B) with A = prod a = k_first-1 ... telescoping: A = k_prev/k_last
    # (0 when k_prev == 0), and B = the block-local scanned b final value.
    a = jnp.where(first_row, 0.0, (k - 1.0) / k)
    b = jnp.where(first_row, 0.0, d2 / k)

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, b1 * a2 + b2

    a_scan, b_scan = jax.lax.associative_scan(combine, (a, b), axis=0)
    block_carry = (a_scan[-1], b_scan[-1])  # this block's composed map
    all_a = jax.lax.all_gather(block_carry[0], axis_name)  # (D,)
    all_b = jax.lax.all_gather(block_carry[1], axis_name)  # (D,)

    # Exclusive associative combine over device blocks (D is tiny: <= 512;
    # a sequential fori over gathered scalars costs nothing).
    def body(i, carry):
        av, bv = carry
        take = i < idx
        a2 = jnp.where(take, all_a[i], 1.0)
        b2 = jnp.where(take, all_b[i], 0.0)
        return av * a2, bv * a2 + b2

    a_prev, b_prev = jax.lax.fori_loop(0, ndev, body, (jnp.float32(1.0),
                                                       jnp.float32(0.0)))
    var_in = b_prev  # global var_0 = 0 (fresh stream)
    del a_prev

    var = a_scan * var_in + b_scan
    var = jnp.where(first_row, 0.0, var)

    # ---- replicated global final state -----------------------------------
    # Every device reduces the same gathered carries, so the result is
    # bitwise-identical everywhere (legitimately replicated).
    k_total = jnp.float32(ndev * t_local)
    mean_total = jnp.sum(all_sums, axis=0) / k_total

    def body_all(i, carry):
        av, bv = carry
        return av * all_a[i], bv * all_a[i] + all_b[i]

    _, var_total = jax.lax.fori_loop(0, ndev, body_all,
                                     (jnp.float32(1.0), jnp.float32(0.0)))

    # ---- verdicts ---------------------------------------------------------
    safe = var > 0.0
    ecc = 1.0 / k + jnp.where(safe, d2 / (k * jnp.where(safe, var, 1.0)), 0.0)
    zeta = ecc / 2.0
    thr = teda_threshold(k, m)
    outlier = jnp.logical_and(zeta > thr, k >= 2.0)

    out = TedaOutput(ecc=ecc, typ=1.0 - ecc, zeta=zeta, threshold=thr,
                     outlier=outlier, k=k)
    final = TedaState(k=k_total, mean=mean_total, var=var_total)
    return final, out


def make_distributed_teda(mesh: Mesh, axis_name: str = "data"):
    """Build a jitted sharded-TEDA callable for `mesh`.

    Returns f(x, m) with x (T, N) sharded (axis_name, None); outputs are
    per-sample verdicts with the same T sharding and a replicated final
    state (every device ends with the full-stream statistics).
    """
    body = functools.partial(_local_shard_scan, axis_name=axis_name)
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name, None), P()),
        out_specs=(TedaState(k=P(), mean=P(), var=P()),
                   TedaOutput(*([P(axis_name)] * 6))),
        check_vma=False,
    )
    x_sh = NamedSharding(mesh, P(axis_name, None))
    m_sh = NamedSharding(mesh, P())
    return jax.jit(mapped, in_shardings=(x_sh, m_sh))


def distributed_teda(x: jnp.ndarray, m, mesh: Mesh, axis_name: str = "data"
                     ) -> Tuple[TedaState, TedaOutput]:
    """One-shot convenience wrapper around make_distributed_teda."""
    fn = make_distributed_teda(mesh, axis_name)
    return fn(x, jnp.asarray(m, jnp.float32))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral_8x7b \
      --shape train_4k --mesh multi                            # one cell
  ... --list  /  --force  /  --out experiments/dryrun

Each cell lowers jit(step).lower(*ShapeDtypeStructs), compiles, and
records memory_analysis / cost_analysis / collective traffic into a JSON
cache (resumable; reruns skip completed cells).
"""
# The first two lines MUST precede any other import: jax locks the device
# count at first initialization.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

import argparse
import json
import time
import traceback

import jax

from repro.configs.registry import ALIASES, SHAPES, all_cells, get_config
from repro.launch.hlo_analysis import collective_stats, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell
from repro.models.common import active_param_count

MESHES = ("single", "multi")

# Per-cell fit overrides (see EXPERIMENTS.md §Perf for the derivations):
# dbrx-132b at fp32 Adam carries 12 B/param of optimizer+param state =
# 6.2 GB/chip on 256 chips; bf16 moments + bf16 grad accumulation bring
# the full train step under the 16 GB HBM budget at production fidelity.
FIT_OVERRIDES = {
    ("dbrx_132b", "train_4k"): {
        "opt_overrides": {"grad_dtype": "bfloat16",
                          "m_dtype": "bfloat16", "v_dtype": "bfloat16"},
    },
    # 132B param+opt state cannot replicate per pod: ZeRO-3 across pods
    ("dbrx_132b", "train_4k", "multi"): {
        "opt_overrides": {"grad_dtype": "bfloat16",
                          "m_dtype": "bfloat16", "v_dtype": "bfloat16"},
        "rule_flags": {"fsdp_over_pod": True},
    },
    # GSPMD converges the decoder-scan carry to batch-replicated without
    # the residual-activation constraint (19.5 GB -> 3.6 GB with it)
    ("seamless_m4t_medium", "train_4k"): {"hints": True},
    # GSPMD batch-replication pathology on big-d prefill (EXPERIMENTS
    # §Perf): the residual constraint restores batch sharding
    ("qwen2_7b", "prefill_32k"): {"hints": True},
    ("chameleon_34b", "prefill_32k"): {"hints": True},
    # SSM-family scan carries also converge batch-replicated
    ("zamba2_2p7b", "train_4k"): {"hints": True},
    ("zamba2_2p7b", "prefill_32k"): {"hints": True},
    ("xlstm_350m", "train_4k"): {"hints": True},
    ("mixtral_8x7b", "train_4k", "multi"): {
        "opt_overrides": {"grad_dtype": "bfloat16",
                          "m_dtype": "bfloat16", "v_dtype": "bfloat16"},
        "rule_flags": {"fsdp_over_pod": True},
    },
}


def _calibration_cfg(cfg, groups: int, sp, unchunk: bool):
    """Unrolled variant with `groups` layer-groups (loop calibration).

    HLO cost analysis counts while-loop bodies ONCE, so the scanned full
    model under-reports flops/bytes/collectives. We compile unrolled
    1-group and 2-group variants and extrapolate linearly in the group
    count — everything outside the layer stack (embed, unembed, loss,
    optimizer) is shared and cancels in the difference.

    Two variants are used:
      * unchunk=True  — single-chunk attention/SSD (NO loops at all):
        exact FLOP counting (flops are schedule-invariant).
      * unchunk=False — production chunking kept: collective counting is
        exact (collectives sit at layer boundaries, never inside chunk
        loops) and byte counts reflect the fused/chunked schedule (chunk
        working sets are VMEM-resident on the TPU target, so counting
        chunk-loop bodies once approximates HBM traffic far better than
        the unchunked variant, whose S^2 score tensors would never be
        materialized to HBM).
    """
    import dataclasses

    from repro.models.transformer import block_layout
    grp, n_groups = block_layout(cfg)
    per_group = cfg.n_layers // n_groups if n_groups else 1
    big = 1 << 30
    over = dict(
        scan_layers=False,
        n_layers=per_group * groups,
        # remat inherited: recompute flops must count, matching the real
        # compiled schedule
    )
    if unchunk:
        over.update(q_chunk=big, kv_chunk=big, ssm_chunk=big)
    if cfg.family == "encdec":
        over["enc_layers"] = groups
        over["dec_layers"] = groups
        over["n_layers"] = 2 * groups
    return dataclasses.replace(cfg, **over), n_groups


def analytic_loop_flops(cfg, sp, n_dev: int) -> float:
    """Per-device executed flops living INSIDE chunk loops, which HLO
    cost analysis counts only once (loop bodies): attention S-quadratic
    terms, SSD/mLSTM intra-chunk terms, chunked-MoE expert matmuls,
    chunked-CE read-out, sLSTM recurrence.

    Multipliers approximate the executed schedule: train = fwd + remat
    recompute + backward(2x fwd) [+1 for the extra q-chunk checkpoint on
    attention]; prefill = fwd only; decode = 0 (its path has no chunk
    loops — the layer scan is handled by the group extrapolation).
    Documented in EXPERIMENTS.md §Dry-run methodology.
    """
    from repro.models.transformer import block_layout

    if sp.kind == "decode":
        return 0.0
    train = sp.kind == "train"
    attn_mult = 5.0 if train else 1.0
    other_mult = 4.0 if train else 1.0

    s = sp.seq_len
    b = sp.global_batch
    hd, h = cfg.head_dim, cfg.n_heads
    total = 0.0

    def attn_term(kv_eff, count):
        return 4.0 * b * h * s * kv_eff * hd * count

    if cfg.family == "encdec":
        total += attn_term(s, cfg.enc_layers) * attn_mult        # enc
        total += attn_term(s / 2, cfg.dec_layers) * attn_mult    # dec self
        total += attn_term(s, cfg.dec_layers) * attn_mult        # cross
    else:
        grp, n_groups = block_layout(cfg)
        for bd in grp:
            if bd.kind in ("attn", "moe", "shared"):
                kv_eff = min(bd.window, s) if bd.window else s / 2
                total += attn_term(kv_eff, n_groups) * attn_mult
            if bd.kind == "ssm":
                q = min(cfg.ssm_chunk, s)
                d_in = cfg.ssm_expand * cfg.d_model
                hs = d_in // cfg.ssm_head_dim
                ps = cfg.ssm_head_dim
                n = cfg.ssm_state
                intra = 2.0 * b * s * q * (n + hs * ps)
                inter = 4.0 * b * s * hs * ps * n
                total += (intra + inter) * n_groups * other_mult
            if bd.kind == "mlstm":
                d_in = int(cfg.mlstm_proj_factor * cfg.d_model)
                pm = d_in // cfg.n_heads
                q = min(cfg.ssm_chunk, s)
                intra = 4.0 * b * s * q * d_in
                state = 4.0 * b * s * d_in * pm
                total += (intra + state) * n_groups * other_mult
            if bd.kind == "slstm":
                ph = cfg.d_model // cfg.n_heads
                total += 8.0 * b * s * cfg.d_model * ph                     * n_groups * other_mult
        # chunked MoE expert matmuls (loop present when tokens > chunk)
        if cfg.family == "moe" and cfg.moe_chunk and b * s > cfg.moe_chunk:
            c_total = b * s * cfg.top_k * cfg.capacity_factor
            total += (3 * 2.0 * c_total * cfg.d_model * cfg.d_ff
                      * cfg.n_layers) * other_mult

    # chunked CE (train only; loop enters when S > ce_chunk)
    if train and cfg.ce_chunk and s > cfg.ce_chunk:
        from repro.models.common import vocab_padded
        total += 2.0 * b * s * cfg.d_model * vocab_padded(cfg) * 4.0

    return total / n_dev


def calibrate_cell(arch, sp, mesh, cfg, n_dev, seq_parallel=None,
                   accum_real: int = 1, opt_cfg=None):
    """Extrapolated per-device flops/bytes/collectives.

    Measurement model (train): F(G, K) = opt + K*outm + K*G*bodym,
    where G = layer-group count, K = microbatch count (accumulation),
    outm = per-micro non-layer work (embed/unembed/CE), bodym =
    per-micro per-group work. Three unrolled compiles — (g=1,k=1),
    (g=2,k=1), (g=1,k=2) — identify the three coefficients; for
    prefill/decode K is fixed at 1 and two compiles suffice. Compiles
    keep the production chunking (collectives sit at layer boundaries,
    never inside chunk loops, so their counting is exact; bytes reflect
    the fused/chunked schedule); the flops that live INSIDE chunk loops
    (attention quadratic terms, SSD/mLSTM intra-chunk, chunked MoE/CE)
    are added back analytically via `analytic_loop_flops`.
    """
    from repro.sharding.hints import activation_hints

    is_train = sp.kind == "train"
    micro_b = max(sp.global_batch // accum_real, 1)

    def measure(g, k, unchunk):
        import contextlib
        ccfg, n_groups = _calibration_cfg(cfg, g, sp, unchunk)
        csp = sp._replace(global_batch=micro_b * k) if is_train else sp
        cell = build_cell(arch, csp, mesh, ccfg,
                          accum_steps=k if is_train else None,
                          unroll_accum=True, opt_cfg=opt_cfg)
        hint_ctx = (activation_hints(mesh, sp=seq_parallel)
                    if seq_parallel is not None else
                    contextlib.nullcontext())
        with mesh, hint_ctx:
            comp = jax.jit(
                cell.fn, in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
                donate_argnums=cell.donate_argnums,
            ).lower(*cell.args).compile()
        cost = comp.cost_analysis()
        coll = collective_stats(comp.as_text())
        return {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll.get("total_bytes", 0.0),
        }, n_groups

    out = {}
    f11, n_groups = measure(1, 1, False)
    f21, _ = measure(2, 1, False)
    if is_train:
        f12, _ = measure(1, 2, False)
    for key in ("flops", "bytes", "coll"):
        bodym = max(f21[key] - f11[key], 0.0)
        if is_train:
            outm = max(f12[key] - f11[key] - bodym, 0.0)
            opt = max(f11[key] - outm - bodym, 0.0)
            out[key] = (opt + accum_real * outm
                        + accum_real * n_groups * bodym)
        else:
            outside = max(f11[key] - bodym, 0.0)
            out[key] = outside + n_groups * bodym
        if key == "flops":
            out["per_group_flops"] = bodym
            out["outside_flops"] = max(f11[key] - bodym, 0.0)
    out["loop_flops_addback"] = analytic_loop_flops(cfg, sp, n_dev)
    out["flops"] += out["loop_flops_addback"]
    out["n_groups"] = n_groups
    out["accum_steps"] = accum_real
    out["micro_batch"] = micro_b
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             cfg_overrides=None, tag: str = "",
             seq_parallel: bool | None = None,
             accum_steps: int | None = None,
             opt_overrides=None, hints: bool = False,
             rule_flags=None) -> dict:

    from repro.launch.specs import pick_accum_steps
    from repro.optim import adamw
    from repro.sharding import rules
    from repro.sharding.hints import activation_hints

    saved_flags = dict(rules.RULE_FLAGS)
    if rule_flags:
        rules.RULE_FLAGS.update(rule_flags)

    sp = next(s for s in SHAPES if s.name == shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    cfg = get_config(arch)
    if cfg_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    if accum_steps is None and sp.kind == "train":
        accum_steps = pick_accum_steps(mesh, sp.global_batch, sp.seq_len,
                                       cfg.d_model)
    accum_steps = accum_steps or 1
    opt_cfg = adamw.AdamWConfig(**(opt_overrides or {}))
    # activation hints are an opt-in experiment knob (GSPMD's default
    # propagation beat both hint modes on the audited cells)
    use_hints = hints or bool(seq_parallel)

    import contextlib
    t0 = time.time()
    cell = build_cell(arch, sp, mesh, cfg, accum_steps=accum_steps,
                      opt_cfg=opt_cfg)
    hint_ctx = (activation_hints(mesh, sp=bool(seq_parallel))
                if use_hints else contextlib.nullcontext())
    with mesh, hint_ctx:
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate_argnums)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cal = calibrate_cell(arch, sp, mesh, cfg, n_dev,
                         seq_parallel=bool(seq_parallel) if use_hints
                         else None,
                         accum_real=accum_steps, opt_cfg=opt_cfg)

    mem = compiled.memory_analysis()
    mem_info = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }
    cost = compiled.cost_analysis()
    flops_raw = float(cost.get("flops", 0.0))  # under-counts loop bodies
    bytes_raw = float(cost.get("bytes accessed", 0.0))

    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    # calibrated per-device numbers (loop-corrected; see calibrate_cell)
    flops = cal["flops"]
    bytes_acc = cal["bytes"]
    coll_bytes = cal["coll"]
    terms = roofline_terms(flops, bytes_acc, coll_bytes)

    model_flops = None
    n_active = active_param_count(cfg)
    if sp.kind == "train":
        model_flops = 6 * n_active * cell.token_count
    elif sp.kind == "prefill":
        model_flops = 2 * n_active * cell.token_count
    else:  # decode: one token per sequence
        model_flops = 2 * n_active * cell.token_count
    useful = model_flops / max(flops * n_dev, 1.0)

    rules.RULE_FLAGS.update(saved_flags)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "rule_flags": rule_flags or {},
        "tag": tag, "devices": n_dev,
        "kind": sp.kind, "seq_len": sp.seq_len,
        "global_batch": sp.global_batch,
        "accum_steps": accum_steps, "seq_parallel": bool(seq_parallel),
        "hints": use_hints, "opt_overrides": opt_overrides or {},
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem_info,
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll_bytes,
        "flops_per_device_raw_scanned": flops_raw,
        "bytes_per_device_raw_scanned": bytes_raw,
        "collectives_scanned_hlo": coll,
        "calibration": cal,
        "roofline": terms,
        "model_flops_6nd": model_flops,
        "useful_flop_ratio": useful,
        "active_params": n_active,
        "token_count": cell.token_count,
    }
    return result


def cell_path(out_dir, arch, shape, mesh_kind, tag=""):
    suffix = f"__{tag}" if tag else ""
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    meshes = MESHES if args.mesh == "both" else (args.mesh,)
    os.makedirs(args.out, exist_ok=True)

    todo = []
    for arch, sp, skip in all_cells():
        if args.arch and ALIASES.get(args.arch, args.arch) != arch:
            continue
        if args.shape and sp.name != args.shape:
            continue
        for mk in meshes:
            todo.append((arch, sp.name, mk, skip))

    if args.list:
        for t in todo:
            print(*t)
        return

    n_ok = n_fail = n_skip = 0
    for arch, shape, mk, skip in todo:
        path = cell_path(args.out, arch, shape, mk)
        if skip:
            with open(path, "w") as f:
                json.dump({"arch": arch, "shape": shape, "mesh": mk,
                           "skipped": True,
                           "reason": "pure full-attention arch at 500k "
                                     "(DESIGN.md long_500k handling)"}, f)
            n_skip += 1
            continue
        if os.path.exists(path) and not args.force:
            print(f"[cached] {arch} {shape} {mk}")
            n_ok += 1
            continue
        print(f"[run] {arch} {shape} {mk} ...", flush=True)
        try:
            over = FIT_OVERRIDES.get((arch, shape, mk),
                                     FIT_OVERRIDES.get((arch, shape), {}))
            res = run_cell(arch, shape, mk, **over)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            r = res["roofline"]
            print(f"  ok compile={res['compile_s']:.1f}s "
                  f"bottleneck={r['bottleneck']} "
                  f"compute={r['compute_s']:.4f}s "
                  f"mem={r['memory_s']:.4f}s "
                  f"coll={r['collective_s']:.4f}s", flush=True)
            n_ok += 1
        except Exception:
            traceback.print_exc()
            with open(path + ".fail", "w") as f:
                f.write(traceback.format_exc())
            n_fail += 1
    print(f"done: ok={n_ok} fail={n_fail} skip={n_skip}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""Dry-run of the paper's technique itself on the production meshes.

Lowers + compiles the distributed TEDA scan (core/distributed.py) for
the single-pod (256-chip) and multi-pod (512-chip) meshes, recording
per-device flops/bytes and collective traffic — proof that one logical
TEDA stream scales across pods with O(devices * N) communication,
independent of stream length (EXPERIMENTS.md §Dry-run/TEDA).

  PYTHONPATH=src python -m repro.launch.teda_dryrun
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.distributed import _local_shard_scan
from repro.launch.hlo_analysis import collective_stats, roofline_terms
from repro.launch.mesh import make_production_mesh


def run(multi_pod: bool, t_total: int, n_feat: int) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    axes = ("pod", "data") if multi_pod else ("data",)

    import functools
    body = functools.partial(_local_shard_scan, m=3.0, axis_name=axes)
    from repro.core.teda import TedaOutput, TedaState
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axes, None),),
        out_specs=(TedaState(k=P(), mean=P(), var=P()),
                   TedaOutput(*([P(axes)] * 6))),
        check_vma=False,
    )
    x = jax.ShapeDtypeStruct((t_total, n_feat), jnp.float32)
    with mesh:
        comp = jax.jit(
            mapped,
            in_shardings=(NamedSharding(mesh, P(axes, None)),),
        ).lower(x).compile()
    cost = comp.cost_analysis()
    coll = collective_stats(comp.as_text())
    mem = comp.memory_analysis()
    terms = roofline_terms(float(cost.get("flops", 0.0)),
                           float(cost.get("bytes accessed", 0.0)),
                           coll.get("total_bytes", 0.0))
    return {
        "mesh": "multi" if multi_pod else "single",
        "devices": n_dev,
        "t_total": t_total, "n_feat": n_feat,
        "t_per_device": t_total // n_dev,
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll,
        "temp_bytes": mem.temp_size_in_bytes,
        "roofline": terms,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--t", type=int, default=1 << 24)  # 16.7M samples
    ap.add_argument("--feat", type=int, default=4)
    ap.add_argument("--out", default="experiments/teda_dryrun.json")
    args = ap.parse_args()
    results = []
    for multi in (False, True):
        r = run(multi, args.t, args.feat)
        results.append(r)
        print(f"[{r['mesh']}] devices={r['devices']} "
              f"T/dev={r['t_per_device']} "
              f"coll_bytes={r['collectives'].get('total_bytes', 0):.0f} "
              f"({r['collectives']}) temp={r['temp_bytes'] / 1e6:.1f}MB")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()

"""Launch-layer tests: mesh builders, cell specs, mini dry-run, train loop,
pipeline parallelism. Multi-device pieces run in subprocesses so the main
pytest process keeps its single CPU device."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

from repro.configs.registry import all_cells, get_config
from repro.launch.train import train

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sub(script: str, timeout=560):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


def test_cell_enumeration():
    cells = list(all_cells())
    assert len(cells) == 40
    assert sum(1 for *_, skip in cells if skip) == 5


def test_train_loop_runs_and_improves():
    cfg = get_config("llama3.2-1b").reduced()
    _, hist, _ = train(cfg, steps=8, batch=4, seq=32, ckpt_dir=None,
                       log_every=100)
    assert len(hist) == 8
    assert all(jnp.isfinite(h["loss"]) for h in hist)


def test_train_checkpoint_resume(tmp_path):
    cfg = get_config("llama3.2-1b").reduced()
    train(cfg, steps=4, batch=2, seq=32, ckpt_dir=str(tmp_path),
          save_every=2)
    _, hist, _ = train(cfg, steps=6, batch=2, seq=32,
                       ckpt_dir=str(tmp_path), resume=True)
    assert len(hist) == 2  # resumed at step 4 of 6


@pytest.mark.slow
def test_mini_dryrun_all_kinds():
    """Lower+compile train/prefill/decode cells on an 8-device mesh with
    reduced configs — the dry-run machinery end-to-end (the production
    16x16 / 2x16x16 sweep runs via python -m repro.launch.dryrun)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.configs.registry import ShapeSpec, get_config
        from repro.launch.specs import build_cell
        from jax.sharding import AxisType
        from repro.launch.hlo_analysis import collective_stats, \
            roofline_terms
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             (AxisType.Auto,) * 2)
        for arch in ("mixtral_8x7b", "zamba2_2p7b", "gemma2_2b"):
            cfg = get_config(arch).reduced()
            for kind, b, s in (("train", 8, 64), ("prefill", 8, 64),
                               ("decode", 8, 64)):
                sp = ShapeSpec(f"mini_{kind}", s, b, kind)
                cell = build_cell(arch, sp, mesh, cfg)
                with mesh:
                    comp = jax.jit(
                        cell.fn, in_shardings=cell.in_shardings,
                        out_shardings=cell.out_shardings,
                        donate_argnums=cell.donate_argnums,
                    ).lower(*cell.args).compile()
                cost = comp.cost_analysis()
                assert float(cost.get("flops", 0)) > 0
                stats = collective_stats(comp.as_text())
                terms = roofline_terms(1e12, 1e9, stats["total_bytes"])
                assert terms["bottleneck"] in ("compute", "memory",
                                               "collective")
                print("OK", arch, kind)
        print("MINI_DRYRUN_OK")
    """)
    out = _sub(script)
    assert "MINI_DRYRUN_OK" in out


@pytest.mark.slow
def test_pipeline_parallel_4stage():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.sharding.pipeline import make_pipelined
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,), ("pipe",), (AxisType.Auto,))
        # 4 affine stages; reference = composed application
        ws = jnp.asarray([[2.0], [0.5], [3.0], [1.0]])  # (S, 1) scales
        def stage(w, x):
            return x * w[0]
        run = make_pipelined(mesh, stage, 4)
        x = jnp.arange(24.0).reshape(6, 4)  # 6 microbatches of 4
        out = run(ws, x)
        ref = x * 2.0 * 0.5 * 3.0 * 1.0
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6)
        print("PIPE_OK")
    """)
    out = _sub(script)
    assert "PIPE_OK" in out


def test_collective_parser():
    from repro.launch.hlo_analysis import collective_stats
    hlo = (
        "%ag = f32[16,1024]{1,0} all-gather(f32[1,1024]{1,0} %p), "
        "replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}\n"
        "%ar = (f32[128]{0}, f32[64]{0}) all-reduce(%a, %b), "
        "replica_groups=[2,8]<=[16]\n"
        "%cp = bf16[32,32]{1,0} collective-permute(%x), "
        "source_target_pairs={{0,1}}\n")
    st = collective_stats(hlo)
    ag = 16 * 1024 * 4 * (15 / 16)
    ar = (128 + 64) * 4 * 2 * (7 / 8)
    cp = 32 * 32 * 2
    assert abs(st["all-gather"] - ag) < 1
    assert abs(st["all-reduce"] - ar) < 1
    assert abs(st["collective-permute"] - cp) < 1
    assert st["all-gather_count"] == 1


@pytest.mark.slow
def test_teda_distributed_dryrun_both_meshes():
    """The paper's technique on the production meshes: compile +
    O(devices) collective traffic, independent of stream length."""
    script = textwrap.dedent("""
        from repro.launch.teda_dryrun import run
        a = run(False, 1 << 20, 4)
        b = run(True, 1 << 20, 4)
        assert a["devices"] == 256 and b["devices"] == 512
        for r in (a, b):
            assert r["collectives"]["total_bytes"] < 10_000  # O(D*N)
            assert r["collectives"]["all-gather_count"] == 3
        print("TEDA_DRYRUN_OK")
    """)
    out = _sub(script)
    assert "TEDA_DRYRUN_OK" in out

"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode on CPU accepts kernels that Mosaic refuses (float iota,
dynamic slices of values, tiles larger than the scoped VMEM), so these
tests lower each kernel the serving engine calls for one chip of a
described `v5e:2x2` topology, with `interpret=False`, and compile it
with the TPU compiler — no chip needed.  Shapes are the chip smoke's:
1,024 and 4,096 slots, the scheduler's (chunk_t=256, block_t=8) chunk
program and its one-sample decode program; and the 16,384-slot
five-member fleet.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.detectors.ensemble import ensemble_scan
from repro.fixedpoint import QFormat
from repro.kernels import ops
from repro.kernels.ops import teda_q_scan_verdict, teda_scan_verdict

FMT = QFormat(32, 20)
K3 = ("teda", "rde", "zscore")
K5 = ("teda", "rde", "zscore", "hst", "teda-q")
SCHED_BLOCK_T = 8  # the block_t BatchingScheduler runs the kernels at

KERNELS = {
    "float": lambda x, bt: teda_scan_verdict(
        x, 3.0, block_t=bt, interpret=False),
    "q": lambda x, bt: teda_q_scan_verdict(
        x, FMT, 3.0, block_t=bt, interpret=False),
    "ensemble-k3": lambda x, bt: ensemble_scan(
        x, 3.0, detectors=K3, block_t=bt, interpret=False),
    "ensemble-k5": lambda x, bt: ensemble_scan(
        x, 3.0, detectors=K5, fmt=FMT, block_t=bt, interpret=False),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, kernel, t, c, block_t):
    x = jax.ShapeDtypeStruct((t, c), jnp.float32, sharding=one_chip)
    fn = KERNELS[kernel]
    compiled = jax.jit(lambda x: fn(x, block_t)).lower(x).compile()
    # the Mosaic kernel is in the program, not an interpreted emulation
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("t", [256, 1], ids=["chunk", "decode"])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_kernel_compiles_at_scheduler_shapes(one_chip, kernel, t):
    _compile(one_chip, kernel, t, 1024, SCHED_BLOCK_T)


def test_wide_pool_default_block_c_fits_vmem(one_chip):
    """4,096 slots at the engine's default block_t=256 overflow VMEM as
    one strip; the default block_c splits them into strips that fit."""
    _compile(one_chip, "ensemble-k5", 256, 4096, 256)


@pytest.mark.parametrize("t,block_t", [(256, SCHED_BLOCK_T),
                                       (1, SCHED_BLOCK_T), (256, 256)],
                         ids=["chunk", "decode", "block_t-256"])
def test_fleet_ensemble_default_block_c_fits_vmem(one_chip, t, block_t):
    """The five-member ensemble at 16,384 slots, on the default block_c
    counted from its VMEM per lane: one 16,384-lane strip, or 8,192-lane
    strips, overflow scoped VMEM at the scheduler's block_t."""
    _compile(one_chip, "ensemble-k5", t, 16384, block_t)


@pytest.mark.parametrize("c,block_t,want", [
    (256, SCHED_BLOCK_T, 0),        # linerate-q: one strip
    (16384, SCHED_BLOCK_T, 8192),
    (256, 256, 0),
    (16384, 256, 256),
])
@pytest.mark.parametrize("kernel", ["float", "q"])
def test_teda_kernels_default_block_c_is_pinned(monkeypatch, kernel, c,
                                                block_t, want):
    """The float and Q verdict kernels keep their tiling: the block_c
    their wrappers pick at linerate-q's (4,096, 256) frame and at
    16,384 slots."""
    seen = []
    real = ops.norm_block_c

    def spy(*args, **kwargs):
        seen.append(real(*args, **kwargs))
        return seen[-1]

    monkeypatch.setattr(ops, "norm_block_c", spy)
    fn = {"float": lambda x: teda_scan_verdict(
              x, 3.0, block_t=block_t, interpret=True),
          "q": lambda x: teda_q_scan_verdict(
              x, FMT, 3.0, block_t=block_t, interpret=True)}[kernel]
    jax.eval_shape(fn, jax.ShapeDtypeStruct((4096, c), jnp.float32))
    assert seen == [want]

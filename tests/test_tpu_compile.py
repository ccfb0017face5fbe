"""The main-path Pallas kernels compiled for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed beside
jax, compiles for a topology that is described and not attached, at the
real widths of ``mrf-fpga`` and ``mrf-original``.  It refuses what the
interpreter accepts and Mosaic does not — block shapes off the (8, 128)
tile grid, ops with no TPU lowering, SMEM or VMEM overflow — so these
tests catch such a kernel before any chip time is spent on it.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest-xdist
worker imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import mrf_net
from repro.kernels.fused_train.kernel import (MAX_LAUNCH_TILES, PAD,
                                              fused_train_call)
from repro.kernels.fused_train.multistep import (fused_train_adam_call,
                                                fused_train_multistep_call)
from repro.kernels.qat_dense.fused import fused_forward_call
from repro.serve.executor import DEFAULT_BUCKETS

BATCH = 256         # engine.train's default per-step batch
CHUNK_STEPS = 16    # benchmarks/run.py's --chunk-steps default
TILE = 128          # EngineConfig.tile_batch default
BLOCK_M = 512       # WaveExecutor's fused block_m default


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _n_layers(arch):
    cfg = get_config(arch)
    return len(mrf_net.layer_sizes(cfg.mrf_n_frames, cfg.mrf_hidden)) - 1


def _train_shapes(one_chip, n_layers, rows, n_moment_pairs=0):
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    stacks = [f32((n_layers, PAD, PAD)), f32((n_layers, PAD))]
    return ([f32((rows, PAD)), f32((rows, PAD))]
            + stacks * (1 + n_moment_pairs))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["mrf-fpga", "mrf-original"])
def test_fused_train_sgd_compiles(arch, one_chip):
    n_layers = _n_layers(arch)
    static = dict(n_layers=n_layers, out_dim=2, lr=1e-3, tile_batch=TILE,
                  interpret=False)
    _assert_kernel(fused_train_call.lower(
        *_train_shapes(one_chip, n_layers, BATCH), **static).compile())
    _assert_kernel(fused_train_multistep_call.lower(
        *_train_shapes(one_chip, n_layers, CHUNK_STEPS * BATCH), qat=True,
        **static).compile())


@pytest.mark.parametrize("arch,tile,n_tiles", [
    ("mrf-fpga", TILE, CHUNK_STEPS * BATCH // TILE),
    ("mrf-original", TILE, CHUNK_STEPS * BATCH // TILE),
    # the longest launch the engine builds: the SMEM carriers at their cap
    ("mrf-fpga", 8, MAX_LAUNCH_TILES),
])
def test_fused_train_adam_compiles(arch, tile, n_tiles, one_chip):
    n_layers = _n_layers(arch)
    step0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = fused_train_adam_call.lower(
        step0, *_train_shapes(one_chip, n_layers, n_tiles * tile, 2),
        n_layers=n_layers, out_dim=2, lr=1e-3, tile_batch=tile,
        interpret=False).compile()
    _assert_kernel(compiled)


def test_fused_int8_forward_compiles(one_chip):
    cfg = get_config("mrf-fpga")
    sizes = mrf_net.layer_sizes(cfg.mrf_n_frames, cfg.mrf_hidden)
    pad = lambda n: -(-n // 128) * 128
    shape = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    packed = []
    for k, n in zip(sizes[:-1], sizes[1:]):
        packed += [shape((pad(k), pad(n)), jnp.int8),
                   shape((1, pad(n)), jnp.int32),
                   shape((1, pad(n)), jnp.float32)]
    denorm = shape((1, pad(sizes[-1])), jnp.float32)
    x = shape((max(DEFAULT_BUCKETS), pad(sizes[0])), jnp.float32)
    s_in = shape((), jnp.float32)
    compiled = fused_forward_call.lower(
        x, s_in, *packed, denorm, n_layers=len(sizes) - 1, block_m=BLOCK_M,
        interpret=False, has_denorm=True).compile()
    _assert_kernel(compiled)

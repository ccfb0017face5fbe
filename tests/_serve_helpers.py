"""Shared fixtures for the serving test suites (test_serve_recon /
test_serve_queue): one smoke-sized calibrated net and feature factory, so
the recipe can't drift between the files.  benchmarks/mrf_serve_bench.py
keeps its own cfg-driven variant (full-size topology from the arch config,
not this fixed smoke net)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mrf_net, qat
from repro.data.pipeline import denormalize_targets
from repro.serve.executor import DEFAULT_BUCKETS, plan_tiles

N_FRAMES = 16  # smoke-sized net: (32, 64, 64, 32, 16, 16, 16, 2)


def calibrated_net(seed=0):
    """(params, qat_state, int8_export) for the smoke net — random weights
    plus observer calibration passes; serving needs no trained net."""
    sizes = mrf_net.layer_sizes(N_FRAMES)
    params = mrf_net.init_params(jax.random.PRNGKey(seed), sizes)
    qs = qat.init_qat_state(len(params))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (64, sizes[0]))
    for _ in range(3):
        _, qs = qat.forward_qat(params, qs, x)
    return params, qs, qat.export_int8(params, qs)


def features(n, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, 2 * N_FRAMES),
                             jnp.float32)


def jitted_float_maps(params, x, buckets=DEFAULT_BUCKETS):
    """The float engine's reference: the same forward, jitted, over the
    same zero-padded bucket tiles.  An eager forward is no oracle — XLA
    rounds a fused program and op-by-op dispatch differently."""
    fwd = jax.jit(lambda t: denormalize_targets(mrf_net.forward(params, t)))
    out = []
    for off, count, bucket in plan_tiles(x.shape[0], buckets):
        tile = jnp.zeros((bucket, x.shape[1]), x.dtype)
        tile = tile.at[:count].set(x[off:off + count])
        out.append(np.asarray(fwd(tile))[:count])
    return np.concatenate(out)

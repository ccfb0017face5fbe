"""int8 dense kernel vs pure-jnp oracle: BIT-EXACT on integer outputs
(the paper's FPGA-vs-Python criterion), exact fp32 on the float head.
Shapes/dtypes swept with hypothesis."""

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import mrf_net, qat
from repro.kernels.qat_dense import ops, ref

jax.config.update("jax_platform_name", "cpu")


def _rand_case(m, k, n, seed):
    kx, kw, kb, ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.randint(kx, (m, k), -128, 128, jnp.int8)
    w = jax.random.randint(kw, (k, n), -128, 128, jnp.int8)
    b = jax.random.randint(kb, (n,), -2048, 2048, jnp.int32)
    s = jax.random.uniform(ks, (n,), jnp.float32, 1e-4, 1e-2)
    return x, w, b, s


@pytest.mark.parametrize("mkn", [(8, 64, 32), (130, 200, 300), (1, 64, 2), (256, 256, 128)])
@pytest.mark.parametrize("relu,float_out", [(True, False), (False, False), (False, True)])
def test_bitexact_vs_oracle(mkn, relu, float_out):
    x, w, b, s = _rand_case(*mkn, seed=hash(mkn) % 100)
    got = ops.qat_dense(x, w, b, s, relu=relu, float_out=float_out)
    want = ref.ref_qat_dense(x, w, b, s, relu=relu, float_out=float_out)
    if float_out:
        assert jnp.array_equal(got, want)
    else:
        assert bool(jnp.all(got == want)), "integer outputs must be bit-exact"


@settings(max_examples=12, deadline=None)
@given(m=st.integers(1, 80), k=st.integers(1, 160), n=st.integers(1, 160),
       relu=st.booleans(), seed=st.integers(0, 2**16))
def test_property_bitexact(m, k, n, relu, seed):
    x, w, b, s = _rand_case(m, k, n, seed)
    got = ops.qat_dense(x, w, b, s, relu=relu, float_out=False, block=64)
    want = ref.ref_qat_dense(x, w, b, s, relu=relu, float_out=False)
    assert bool(jnp.all(got == want))


def test_full_integer_network_paths_agree():
    """QAT export -> software integer oracle == Pallas integer network."""
    sizes = mrf_net.layer_sizes(32)
    params = mrf_net.init_params(jax.random.PRNGKey(1), sizes)
    qs = qat.init_qat_state(len(params))
    x = jax.random.normal(jax.random.PRNGKey(2), (32, sizes[0]))
    for _ in range(5):
        _, qs = qat.forward_qat(params, qs, x)
    ints = qat.export_int8(params, qs)
    y_sw = qat.int_forward(ints, x)
    y_pl = ops.int_forward_pallas(ints, x)
    assert jnp.array_equal(y_sw, y_pl)


def test_int_node_bitexact():
    """Paper §2.2: the single-node function on the accelerator must equal the
    software implementation exactly for identical inputs/weights/bias."""
    x, w, b, s = _rand_case(16, 64, 16, seed=7)
    got = ops.qat_dense(x, w, b, s, relu=True)
    want = ref.ref_qat_dense(x, w, b, s, relu=True)
    assert bool(jnp.all(got == want))


# ---------------------------------------------------------------------------
# Fused whole-network kernel + vectorized lax fallback (this PR's paths).
# ---------------------------------------------------------------------------

def _exported_net(seed: int = 1, n_frames: int = 32):
    sizes = mrf_net.layer_sizes(n_frames)
    params = mrf_net.init_params(jax.random.PRNGKey(seed), sizes)
    qs = qat.init_qat_state(len(params))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (64, sizes[0]))
    for _ in range(5):
        _, qs = qat.forward_qat(params, qs, x)
    return qat.export_int8(params, qs), sizes[0]


@pytest.mark.parametrize("relu,float_out", [(True, False), (False, False),
                                            (True, True), (False, True)])
@pytest.mark.parametrize("mkn", [(1, 64, 2), (7, 33, 5), (130, 200, 300),
                                 (128, 128, 128)])
def test_qat_dense_lax_bitexact(mkn, relu, float_out):
    """The pure-lax layer primitive matches the oracle for every epilogue
    combo on ragged AND tile-aligned shapes."""
    x, w, b, s = _rand_case(*mkn, seed=hash(mkn) % 100 + 1)
    got = ops.qat_dense_lax(x, w, b, s, relu=relu, float_out=float_out)
    want = ref.ref_qat_dense(x, w, b, s, relu=relu, float_out=float_out)
    assert got.dtype == want.dtype
    assert jnp.array_equal(got, want)


def test_qat_dense_lax_int32_fallback_bitexact():
    """A bias too large for exact f32 accumulation flips the layer onto the
    int32 dot path — still bit-exact vs the oracle."""
    x, w, b, s = _rand_case(16, 64, 16, seed=3)
    b = b + jnp.int32(2 ** 24)  # k*2**14 + |b| >= 2**24: f32 not exact
    assert not ops._f32_dot_is_exact(64, b)
    got = ops.qat_dense_lax(x, w, b, s, relu=True)
    want = ref.ref_qat_dense(x, w, b, s, relu=True)
    assert jnp.array_equal(got, want)


@pytest.mark.parametrize("m", [1, 7, 96, 128, 333, 1024])
def test_all_int8_impls_bitexact_vs_oracle(m):
    """Fused kernel, lax fallback, layered chain (prepadded and legacy):
    every serving implementation equals ``qat.int_forward`` bit-for-bit on
    ragged and bucket-aligned voxel counts — the paper's FPGA-vs-Python
    criterion for the whole network."""
    ints, in_dim = _exported_net()
    x = jax.random.normal(jax.random.PRNGKey(m), (m, in_dim), jnp.float32)
    want = qat.int_forward(ints, x)
    pre = ops.prepad_int_layers(ints)
    assert jnp.array_equal(want, ops.int_forward_fused(pre, x))
    assert jnp.array_equal(want, ops.int_forward_lax(ints, x))
    assert jnp.array_equal(want, ops.int_forward_pallas(ints, x,
                                                        prepadded=pre))
    assert jnp.array_equal(want, ops.int_forward_pallas(ints, x))


def test_fused_denorm_epilogue_bitexact():
    """The in-kernel denormalize epilogue == composing denormalize_targets
    outside, bit-for-bit (it multiplies after the head scale, never folded
    into it — folding would change f32 rounding)."""
    from repro.data.pipeline import (T1_RANGE_MS, T2_RANGE_MS,
                                     denormalize_targets)

    ints, in_dim = _exported_net(seed=4)
    x = jax.random.normal(jax.random.PRNGKey(9), (75, in_dim), jnp.float32)
    pre = ops.prepad_int_layers(ints)
    dscale = jnp.array([T1_RANGE_MS[1], T2_RANGE_MS[1]], jnp.float32)
    got = ops.int_forward_fused(pre, x, denorm_scale=dscale)
    want = denormalize_targets(qat.int_forward(ints, x))
    assert jnp.array_equal(got, want)


def test_fused_accepts_raw_layer_list_and_block_m():
    """Convenience path (un-prepadded list) and a non-default voxel tile
    both reduce to the same bits."""
    ints, in_dim = _exported_net(seed=5)
    x = jax.random.normal(jax.random.PRNGKey(11), (50, in_dim), jnp.float32)
    want = qat.int_forward(ints, x)
    assert jnp.array_equal(want, ops.int_forward_fused(ints, x))
    assert jnp.array_equal(
        want, ops.int_forward_fused(ops.prepad_int_layers(ints), x,
                                    block_m=16))


def test_prepad_preserves_oracle_scale_grouping():
    """prepad must precompute (s_in * s_w) / s_out with the oracle's operand
    grouping — any re-association changes f32 bits."""
    ints, _ = _exported_net(seed=6)
    pre = ops.prepad_int_layers(ints)
    for i, layer in enumerate(ints):
        n = layer.w_q.shape[1]
        want = (layer.s_in * layer.s_w if layer.s_out is None
                else (layer.s_in * layer.s_w) / layer.s_out)
        assert jnp.array_equal(pre.packed[3 * i + 2][0, :n],
                               want.astype(jnp.float32))
    assert pre.in_dim == int(ints[0].w_q.shape[0])
    assert pre.out_dim == int(ints[-1].w_q.shape[1])
    assert all(w % 128 == 0 for w in pre.padded_widths)

"""Flash attention kernel vs naive oracle: causal, sliding-window, GQA
grouping, padding, block-size sweeps (hypothesis)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attn.ops import flash_attention
from repro.kernels.flash_attn.ref import ref_attention

jax.config.update("jax_platform_name", "cpu")


def _case(b, s, hq, hkv, dh, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, dh), dtype)
    return q, k, v


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (6, 2)])
@pytest.mark.parametrize("causal", [True, False])
def test_matches_oracle(hq, hkv, causal):
    q, k, v = _case(2, 64, hq, hkv, 16)
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    want = ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [8, 24])
def test_sliding_window(window):
    q, k, v = _case(1, 96, 4, 2, 8, seed=1)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=16, block_k=16)
    want = ref_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ragged_seq_padding():
    q, k, v = _case(1, 50, 2, 2, 8, seed=2)  # not a block multiple
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    want = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(s=st.integers(8, 96), hkv=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2, 3]), dh=st.sampled_from([8, 16]),
       bq=st.sampled_from([8, 16, 32]), seed=st.integers(0, 2**16))
def test_property_shapes(s, hkv, g, dh, bq, seed):
    q, k, v = _case(1, s, hkv * g, hkv, dh, seed=seed)
    got = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bq)
    want = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-5)


def test_bf16_io():
    q, k, v = _case(1, 64, 4, 2, 16, seed=3, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    want = ref_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), rtol=2e-2, atol=2e-2)

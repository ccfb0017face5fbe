"""Unit/property tests for model substrates: SSD chunked-vs-sequential,
chunked attention vs naive full softmax, MoE routing invariants, RoPE, QAT."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models.common import apply_rope, fake_quant_int8
from repro.models.moe import moe_block, init_moe
from repro.models.common import key_iter

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------------------
# SSD: the chunked dual form must equal the naive sequential recurrence.
# --------------------------------------------------------------------------

def _ssd_sequential(x, dt, A, B, C):
    b, l, h, p = x.shape
    n = B.shape[-1]

    def step(hstate, t):
        decay = jnp.exp(dt[:, t] * A[None, :])                      # (B,H)
        upd = jnp.einsum("bh,bn,bhp->bhpn", dt[:, t], B[:, t], x[:, t])
        hstate = decay[:, :, None, None] * hstate + upd
        y = jnp.einsum("bn,bhpn->bhp", C[:, t], hstate)
        return hstate, y

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    hT, ys = jax.lax.scan(step, h0, jnp.arange(l))
    return ys.transpose(1, 0, 2, 3), hT


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_ssd_chunked_equals_sequential(chunk):
    key = jax.random.PRNGKey(0)
    b, l, h, p, n = 2, 16, 3, 4, 5
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, l, n))
    C = jax.random.normal(ks[4], (b, l, n))
    y_seq, h_seq = _ssd_sequential(x, dt, A, B, C)
    y_chk, h_chk = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(y_chk, y_seq, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h_chk, h_seq, rtol=2e-4, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(l=st.integers(2, 24), chunk=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 2**16))
def test_property_ssd_any_length(l, chunk, seed):
    if l % chunk:
        l = (l // chunk + 1) * chunk
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    b, h, p, n = 1, 2, 3, 4
    x = jax.random.normal(ks[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, l, n))
    C = jax.random.normal(ks[4], (b, l, n))
    y_seq, _ = _ssd_sequential(x, dt, A, B, C)
    y_chk, _ = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(y_chk, y_seq, rtol=5e-4, atol=5e-5)


# --------------------------------------------------------------------------
# Attention: chunked path vs naive softmax; GQA; SWA; decode split semantics.
# --------------------------------------------------------------------------

def _naive(q, k, v, causal=True, window=None):
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=2)
    vv = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(dh)
    qpos, kpos = jnp.arange(sq), jnp.arange(k.shape[1])
    keep = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        keep &= kpos[None] <= qpos[:, None]
    if window is not None:
        keep &= kpos[None] > qpos[:, None] - window
    s = jnp.where(keep[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (6, 2)])
@pytest.mark.parametrize("window", [None, 5])
def test_attention_matches_naive(hq, hkv, window):
    key = jax.random.PRNGKey(1)
    b, s, dh = 2, 16, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, hq, dh))
    k = jax.random.normal(ks[1], (b, s, hkv, dh))
    v = jax.random.normal(ks[2], (b, s, hkv, dh))
    got = attn.attention(q, k, v, causal=True, window=window, q_chunk=4)
    want = _naive(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_decode_attention_matches_naive_row():
    """Single-token decode == last row of full attention."""
    key = jax.random.PRNGKey(2)
    b, s, hq, hkv, dh = 2, 12, 4, 2, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, hq, dh))
    k = jax.random.normal(ks[1], (b, s, hkv, dh))
    v = jax.random.normal(ks[2], (b, s, hkv, dh))
    full = _naive(q, k, v, causal=True)
    got = attn.decode_attention(q[:, -1], k, v, jnp.int32(s))
    np.testing.assert_allclose(got, full[:, -1], rtol=1e-4, atol=1e-5)


def test_rope_relative_shift_invariance():
    """RoPE: q.k depends only on relative distance."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 1, 1, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, 16))
    def dot_at(p_q, p_k):
        qr = apply_rope(q, jnp.array([[p_q]]))
        kr = apply_rope(k, jnp.array([[p_k]]))
        return float(jnp.sum(qr * kr))
    assert abs(dot_at(5, 3) - dot_at(105, 103)) < 1e-3
    assert abs(dot_at(5, 3) - dot_at(6, 3)) > 1e-4  # sanity: not constant


# --------------------------------------------------------------------------
# MoE invariants
# --------------------------------------------------------------------------

def test_moe_capacity_and_combine():
    key = jax.random.PRNGKey(4)
    keys = key_iter(key)
    d, ff, e = 16, 32, 4
    p = init_moe(keys, d, ff, e, n_shared=0)
    x = jax.random.normal(next(keys), (2, 8, d), jnp.float32)
    y, aux = moe_block(p, x, top_k=2, capacity_factor=2.0, group_size=8)
    assert y.shape == x.shape
    assert jnp.all(jnp.isfinite(y))
    assert float(aux) >= 1.0 - 1e-3  # load-balance loss lower bound is 1 (k=1 term)


def test_moe_grads_reach_all_experts_eventually():
    key = jax.random.PRNGKey(5)
    keys = key_iter(key)
    d, ff, e = 8, 16, 4
    p = init_moe(keys, d, ff, e, n_shared=1)

    def loss(p, x):
        y, aux = moe_block(p, x, top_k=2, capacity_factor=2.0, group_size=32)
        return jnp.mean(jnp.square(y)) + 0.01 * aux

    x = jax.random.normal(next(keys), (4, 32, d), jnp.float32)
    g = jax.grad(loss)(p, x)
    assert bool(jnp.any(g.router != 0))
    assert bool(jnp.any(g.w_in != 0))


# --------------------------------------------------------------------------
# LM-scale QAT forward (the paper's technique knob)
# --------------------------------------------------------------------------

def test_fake_quant_bounds_and_ste():
    x = jnp.array([-3.0, -0.01, 0.0, 0.5, 2.9])
    q = fake_quant_int8(x)
    assert jnp.max(jnp.abs(q - x)) <= jnp.max(jnp.abs(x)) / 127.0 + 1e-6
    g = jax.grad(lambda t: jnp.sum(fake_quant_int8(t) ** 2))(x)
    assert jnp.all(jnp.isfinite(g)) and bool(jnp.any(g != 0))  # STE passes grads


def test_qat_lm_trains():
    import dataclasses
    from repro.configs import get_smoke
    from repro.models import registry
    cfg = dataclasses.replace(get_smoke("tinyllama-1.1b"), quant="qat-int8")
    fns = registry.build(cfg, tp=1)
    key = jax.random.PRNGKey(0)
    params = fns.init(key)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab_size, jnp.int32)
    loss, grads = jax.value_and_grad(fns.loss)(params, {"tokens": tokens,
                                                        "labels": tokens})
    assert jnp.isfinite(loss)
    assert all(jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads))

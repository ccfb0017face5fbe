"""Fused training kernel vs the jax.grad oracle (kernels/fused_train/ref.py).

The paper's correctness criterion is exact agreement between the accelerator
and the Python reference at node granularity; here the entire fused
fwd+bwd+SGD step is checked against autodiff to fp32 tolerance, across batch
tiles, stream (per-sample) mode, and the QAT fake-quant forward.
"""

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import mrf_net
from repro.kernels.fused_train import ops, ref

jax.config.update("jax_platform_name", "cpu")


def _setup(n_frames=32, batch=32, seed=0, hidden=mrf_net.ADAPTED_HIDDEN):
    sizes = mrf_net.layer_sizes(n_frames, hidden)
    params = mrf_net.init_params(jax.random.PRNGKey(seed), sizes)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (batch, sizes[0]))
    y = jax.random.uniform(jax.random.PRNGKey(seed + 2), (batch, 2))
    return params, x, y


def _assert_params_close(a, b, atol=1e-5):
    for la, lb in zip(a, b):
        assert jnp.allclose(la["w"], lb["w"], atol=atol), float(jnp.max(jnp.abs(la["w"] - lb["w"])))
        assert jnp.allclose(la["b"], lb["b"], atol=atol)


@pytest.mark.parametrize("tile_batch", [1, 8, 32])
def test_matches_autodiff_oracle(tile_batch):
    params, x, y = _setup()
    new_k, loss_k = ops.fused_train_step(params, x, y, lr=1e-2, tile_batch=tile_batch)
    new_r, loss_r = ref.ref_train(params, x, y, lr=1e-2, tile_batch=tile_batch)
    assert jnp.allclose(loss_k, loss_r, atol=1e-5)
    _assert_params_close(new_k, new_r)


def test_stream_mode_is_paper_sgd():
    """tile_batch=1 must equal a hand-rolled per-sample SGD loop."""
    params, x, y = _setup(batch=8)
    new_k, _ = ops.fused_train_step(params, x, y, lr=5e-3, tile_batch=1)
    p = params
    for i in range(x.shape[0]):
        g = jax.grad(mrf_net.mse_loss)(p, x[i:i + 1], y[i:i + 1])
        p = jax.tree.map(lambda a, b: a - 5e-3 * b, p, g)
    _assert_params_close(new_k, p)


def test_qat_forward_mode():
    params, x, y = _setup()
    new_k, loss_k = ops.fused_train_step(params, x, y, lr=1e-2, tile_batch=16, qat=True)
    new_r, loss_r = ref.ref_train(params, x, y, lr=1e-2, tile_batch=16, qat=True)
    assert jnp.allclose(loss_k, loss_r, atol=1e-5)
    _assert_params_close(new_k, new_r)


def test_padding_is_inert():
    """Padded lanes must stay exactly zero after a training pass."""
    params, x, y = _setup()
    w_pad, b_pad = ops.pad_params(params)
    from repro.kernels.fused_train.kernel import fused_train_call, PAD
    x_pad = jnp.zeros((32, PAD)).at[:, :x.shape[1]].set(x)
    y_pad = jnp.zeros((32, PAD)).at[:, :2].set(y)
    w_new, b_new, _ = fused_train_call(x_pad, y_pad, w_pad, b_pad,
                                       n_layers=len(params), out_dim=2,
                                       lr=1e-2, tile_batch=8)
    sizes = [p["w"].shape for p in params]
    for l, (i, o) in enumerate(sizes):
        assert jnp.all(w_new[l, i:, :] == 0.0)
        assert jnp.all(w_new[l, :, o:] == 0.0)
        assert jnp.all(b_new[l, o:] == 0.0)


def test_loss_decreases_over_tiles():
    """Sequential SGD across tiles should reduce loss on average."""
    params, x, y = _setup(batch=512, seed=3)
    _, losses = ops.fused_train_step(params, x, y, lr=1e-1, tile_batch=32)
    first, last = float(losses[0]), float(losses[-1])
    assert last < first


@settings(max_examples=8, deadline=None)
@given(
    n_frames=st.sampled_from([8, 16, 32, 64]),
    batch=st.sampled_from([4, 16, 32]),
    tile=st.sampled_from([1, 2, 4]),
    seed=st.integers(0, 2**16),
)
def test_property_kernel_equals_oracle(n_frames, batch, tile, seed):
    if batch % tile:
        tile = 1
    hidden = (32, 16, 16)
    params, x, y = _setup(n_frames=n_frames, batch=batch, seed=seed, hidden=hidden)
    new_k, loss_k = ops.fused_train_step(params, x, y, lr=1e-2, tile_batch=tile)
    new_r, loss_r = ref.ref_train(params, x, y, lr=1e-2, tile_batch=tile)
    assert jnp.allclose(loss_k, loss_r, atol=1e-4)
    _assert_params_close(new_k, new_r, atol=1e-4)


# --------------------------------------------------------------------------
# multi-step launches (multistep.py): K steps per kernel call must be
# BIT-identical to K single-step calls — params, opt state, per-step losses
# --------------------------------------------------------------------------

def _params_bitequal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert jnp.array_equal(la, lb), float(jnp.max(jnp.abs(la - lb)))


def _multi_setup(K=4, batch=24, seed=0):
    params, x, y = _setup(n_frames=16, batch=K * batch, seed=seed,
                          hidden=(32, 16))
    return params, x, y, K, batch


@pytest.mark.parametrize("qat", [False, True])
def test_multistep_sgd_bitmatches_sequential_calls(qat):
    """One K-step launch == K sequential fused_train_step calls, bit for
    bit: final params AND the per-step loss trace (the weights never leave
    VMEM mid-launch, but the grid sequencing makes that unobservable)."""
    params, x, y, K, B = _multi_setup()
    p_multi, _, trace = ops.fused_train_multistep(
        params, None, x, y, n_steps=K, lr=1e-2, optimizer="sgd",
        tile_batch=8, qat=qat)
    p_seq, rows = params, []
    for k in range(K):
        p_seq, losses = ops.fused_train_step(
            p_seq, x[k * B:(k + 1) * B], y[k * B:(k + 1) * B], lr=1e-2,
            tile_batch=8, qat=qat)
        rows.append(losses)
    assert trace.shape == (K, B // 8)
    assert jnp.array_equal(trace, jnp.stack(rows))
    _params_bitequal(p_multi, p_seq)


@pytest.mark.parametrize("qat", [False, True])
def test_multistep_adam_bitmatches_sequential_launches(qat):
    """In-kernel Adam: one K-step launch == K single-step (n_steps=1)
    launches — params, moment stacks, step counter, loss trace.  The moments
    roundtrip through HBM between sequential launches; resident-in-VMEM must
    be unobservable."""
    from repro.optim.optimizers import adam
    params, x, y, K, B = _multi_setup(seed=2)
    opt = adam(2e-3)
    p_multi, st_multi, trace = ops.fused_train_multistep(
        params, opt.init(params), x, y, n_steps=K, lr=2e-3,
        optimizer="adam", tile_batch=8, qat=qat)
    p_seq, st_seq, rows = params, opt.init(params), []
    for k in range(K):
        p_seq, st_seq, tl = ops.fused_train_multistep(
            p_seq, st_seq, x[k * B:(k + 1) * B], y[k * B:(k + 1) * B],
            n_steps=1, lr=2e-3, optimizer="adam", tile_batch=8, qat=qat)
        rows.append(tl[0])
    assert jnp.array_equal(trace, jnp.stack(rows))
    _params_bitequal(p_multi, p_seq)
    _params_bitequal(st_multi.mu, st_seq.mu)
    _params_bitequal(st_multi.nu, st_seq.nu)
    assert int(st_multi.step) == int(st_seq.step) == K * (B // 8)


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_multistep_ragged_chunk_composition(optimizer):
    """Chunk clipping (ft.runner semantics): 4+4+2 multi-step launches must
    bit-match one 10-step launch — a restart landing on any chunk boundary
    resumes the exact trajectory."""
    from repro.optim.optimizers import adam
    B = 16
    params, x, y = _setup(n_frames=16, batch=10 * B, seed=5, hidden=(32, 16))
    st0 = adam(1e-3).init(params) if optimizer == "adam" else None
    p_full, st_full, trace_full = ops.fused_train_multistep(
        params, st0, x, y, n_steps=10, lr=1e-3, optimizer=optimizer,
        tile_batch=8)
    p, st, rows = params, st0, []
    for lo, hi in ((0, 4), (4, 8), (8, 10)):
        p, st, tl = ops.fused_train_multistep(
            p, st, x[lo * B:hi * B], y[lo * B:hi * B], n_steps=hi - lo,
            lr=1e-3, optimizer=optimizer, tile_batch=8)
        rows.append(tl)
    assert jnp.array_equal(trace_full, jnp.concatenate(rows))
    _params_bitequal(p_full, p)
    if optimizer == "adam":
        _params_bitequal(st_full.mu, st.mu)
        _params_bitequal(st_full.nu, st.nu)
        assert int(st_full.step) == int(st.step)


class _ListRefs:
    """List-backed stand-in for the kernel's VMEM scratch refs, so
    ``train_tile`` can run as plain traced JAX for oracle tests."""

    def __init__(self, arrs):
        self.a = [jnp.asarray(v) for v in arrs]

    def __getitem__(self, l):
        return self.a[l]

    def __setitem__(self, l, v):
        self.a[l] = v


def test_adam_kernel_matches_software_adam_on_padded_math():
    """The in-kernel Adam against ``optim.optimizers.adam`` applied to the
    padded stacks, with gradients extracted from the *same* ``train_tile``
    body.  The first update is checked bit-for-bit on the loss and both
    moment stacks (same ops, same order); the parameter subtraction crosses
    two separately-compiled XLA programs where FMA contraction may differ,
    so params — and everything downstream of them over the K-step
    trajectory — are held to float32-ulp tolerance instead."""
    from repro.kernels.fused_train.kernel import PAD, train_tile
    from repro.optim.optimizers import adam
    K, B, tile, out_dim = 3, 16, 8, 2
    params, x, y = _setup(n_frames=16, batch=K * B, seed=7, hidden=(32, 16))
    n_layers = len(params)
    opt = adam(2e-3)
    p_k, st_k, trace = ops.fused_train_multistep(
        params, opt.init(params), x, y, n_steps=K, lr=2e-3,
        optimizer="adam", tile_batch=tile)

    w_pad, b_pad = ops.pad_params(params)
    x_pad = jnp.zeros((K * B, PAD)).at[:, :x.shape[1]].set(x)
    y_pad = jnp.zeros((K * B, PAD)).at[:, :out_dim].set(y)

    @jax.jit
    def software_adam(w_pad, b_pad, x_pad, y_pad):
        stacks = {"w": w_pad, "b": b_pad}
        st = opt.init(stacks)
        losses = []
        for t in range(K * B // tile):
            xs = x_pad[t * tile:(t + 1) * tile]
            ys = y_pad[t * tile:(t + 1) * tile]
            w_s = _ListRefs([stacks["w"][l] for l in range(n_layers)])
            b_s = _ListRefs([stacks["b"][l] for l in range(n_layers)])
            h_s = _ListRefs([jnp.zeros((tile, PAD))] * max(n_layers - 1, 1))
            grads = {"w": [None] * n_layers, "b": [None] * n_layers}

            def grab(l, dw, db):
                grads["w"][l] = dw
                grads["b"][l] = db
            losses.append(train_tile(xs, ys, w_s, b_s, h_s, grab,
                                     n_layers=n_layers, out_dim=out_dim,
                                     qat=False))
            grads = {"w": jnp.stack(grads["w"]), "b": jnp.stack(grads["b"])}
            stacks, st = opt.update(grads, st, stacks)
        return stacks, st, jnp.stack(losses)

    stacks_r, st_r, losses_r = software_adam(w_pad, b_pad, x_pad, y_pad)

    # --- first update: gradient path and moment math are bit-identical -----
    @jax.jit
    def software_first_update(w_pad, b_pad, x_pad, y_pad):
        st = opt.init({"w": w_pad, "b": b_pad})
        w_s = _ListRefs([w_pad[l] for l in range(n_layers)])
        b_s = _ListRefs([b_pad[l] for l in range(n_layers)])
        h_s = _ListRefs([jnp.zeros((tile, PAD))] * max(n_layers - 1, 1))
        grads = {"w": [None] * n_layers, "b": [None] * n_layers}

        def grab(l, dw, db):
            grads["w"][l] = dw
            grads["b"][l] = db
        loss = train_tile(x_pad[:tile], y_pad[:tile], w_s, b_s, h_s, grab,
                          n_layers=n_layers, out_dim=out_dim, qat=False)
        grads = {"w": jnp.stack(grads["w"]), "b": jnp.stack(grads["b"])}
        _, st = opt.update(grads, st, {"w": w_pad, "b": b_pad})
        return loss, st

    loss_1r, st_1r = software_first_update(w_pad, b_pad, x_pad, y_pad)
    _, st1, trace1 = ops.fused_train_multistep(
        params, opt.init(params), x[:tile], y[:tile], n_steps=1, lr=2e-3,
        optimizer="adam", tile_batch=tile)
    assert jnp.array_equal(trace1[0, 0], loss_1r)
    mw1, mb1 = ops.pad_params(st1.mu)
    vw1, vb1 = ops.pad_params(st1.nu)
    assert jnp.array_equal(st_1r.mu["w"], mw1)
    assert jnp.array_equal(st_1r.mu["b"], mb1)
    assert jnp.array_equal(st_1r.nu["w"], vw1)
    assert jnp.array_equal(st_1r.nu["b"], vb1)

    # --- K-step trajectory: float32-ulp agreement --------------------------
    assert jnp.allclose(trace, losses_r.reshape(K, -1), atol=0.0, rtol=1e-5)
    mw_k, mb_k = ops.pad_params(st_k.mu)
    vw_k, vb_k = ops.pad_params(st_k.nu)
    for got, want in ((mw_k, st_r.mu["w"]), (mb_k, st_r.mu["b"]),
                      (vw_k, st_r.nu["w"]), (vb_k, st_r.nu["b"])):
        assert jnp.allclose(got, want, atol=1e-6, rtol=1e-5)
    w_k, b_k = ops.pad_params(p_k)
    assert jnp.allclose(stacks_r["w"], w_k, atol=1e-6, rtol=1e-5)
    assert jnp.allclose(stacks_r["b"], b_k, atol=1e-6, rtol=1e-5)

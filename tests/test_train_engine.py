"""Unified-engine tests: the MRF nets registered as first-class archs, the
three backends (float / qat-int8 / fused-pallas) through one
``(state, batch) -> (state, metrics)`` contract, equivalence against the
historical hand-rolled loops, and the launcher end to end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_smoke
from repro.configs.base import param_count
from repro.core import mrf_net, qat
from repro.core.train_loop import TrainConfig, train
from repro.data.epg import default_sequence
from repro.data.pipeline import MRFSampleStream, sample_batch
from repro.models import registry
from repro.optim import adam, sgd
from repro.train import engine
from repro.train.step import init_train_state

jax.config.update("jax_platform_name", "cpu")


def _params_equal(a, b, atol=0.0):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol,
                                   rtol=0.0)


# --------------------------------------------------------------------------
# registration: the paper's nets are ordinary archs
# --------------------------------------------------------------------------

def test_mrf_archs_registered():
    for name in ("mrf-fpga", "mrf-original"):
        assert name in ARCHS
        cfg = get_smoke(name)
        assert cfg.family == "mrf"
        fns = registry.build(cfg)
        params = fns.init(jax.random.PRNGKey(0))
        assert param_count(cfg) == mrf_net.param_count(params)
        # the analytic count knows the adapted net is the original minus two
        assert param_count(ARCHS["mrf-original"].CONFIG) > param_count(
            ARCHS["mrf-fpga"].CONFIG)


def test_mrf_prefill_is_inference_and_no_decode():
    cfg = get_smoke("mrf-fpga")
    fns = registry.build(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 2 * cfg.mrf_n_frames))
    _, pred = fns.prefill(params, {"x": x})
    assert pred.shape == (4, 2)
    with pytest.raises(NotImplementedError):
        fns.decode(params, None, None, 0)


# --------------------------------------------------------------------------
# backend equivalence vs the historical hand-rolled loops
# --------------------------------------------------------------------------

def test_float_engine_matches_handrolled_adam_loop():
    """train() (engine + ft.runner) must reproduce the pre-refactor loop
    bit-for-bit: same init split, same per-step batch keys, un-clipped Adam."""
    hidden = (32, 16)
    cfg = TrainConfig(n_frames=16, hidden=hidden, steps=8, lr=1e-3,
                      batch_size=32, log_every=100)
    params_e, _, info = train(cfg, verbose=False)

    # the original core/train_loop.train() body, verbatim semantics
    stream = MRFSampleStream(seq=default_sequence(16), batch_size=32)
    sizes = mrf_net.layer_sizes(16, hidden)
    key = jax.random.PRNGKey(0)
    key, k_init = jax.random.split(key)
    params = mrf_net.init_params(k_init, sizes)
    opt = adam(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(mrf_net.mse_loss)(params, x, y)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    for i in range(8):
        x, y = sample_batch(stream, jax.random.fold_in(key, i))
        params, opt_state, loss = step(params, opt_state, x, y)

    _params_equal(params_e, params)
    assert info["sizes"] == sizes


def test_qat_engine_step_matches_handrolled_qat_step():
    """One qat-int8 engine step == the pre-refactor QAT step, exactly:
    has_aux value_and_grad over the fake-quant forward, then Adam."""
    cfg = get_smoke("mrf-fpga")
    fns = registry.build(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    qstate = qat.init_qat_state(len(params))
    opt = adam(1e-3)
    stream = MRFSampleStream(seq=default_sequence(cfg.mrf_n_frames),
                             batch_size=32)
    x, y = sample_batch(stream, jax.random.PRNGKey(5))

    def loss_fn(params, qstate, x, y):
        pred, new_qstate = qat.forward_qat(params, qstate, x, train=True)
        return jnp.mean(jnp.square(pred - y)), new_qstate

    # the pre-refactor core.train_loop QAT step, verbatim (incl. the jit)
    @jax.jit
    def ref_step(params, qstate, opt_state, x, y):
        (loss, new_qstate), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, qstate, x, y)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, new_qstate, opt_state, loss

    params_r, new_qstate_r, _, loss_r = ref_step(params, qstate,
                                                 opt.init(params), x, y)

    step_fn, _ = engine.build(fns, engine.EngineConfig(
        backend="qat-int8", lr=1e-3, max_grad_norm=None, donate=False))
    state = init_train_state(params, opt, aux=qstate)
    new_state, metrics = step_fn(state, {"x": x, "y": y})

    np.testing.assert_array_equal(np.asarray(metrics["loss"]),
                                  np.asarray(loss_r))
    _params_equal(new_state.params, params_r)
    np.testing.assert_array_equal(np.asarray(new_state.aux["act_absmax"]),
                                  np.asarray(new_qstate_r["act_absmax"]))


def test_fused_engine_step_matches_float_reference():
    """One fused-pallas engine step (tile_batch=128 -> a single tile, so one
    minibatch-SGD update) must match the float reference step with SGD."""
    cfg = get_smoke("mrf-fpga")
    fns = registry.build(cfg)
    key = jax.random.PRNGKey(0)
    stream = MRFSampleStream(seq=default_sequence(cfg.mrf_n_frames),
                             batch_size=128)
    x, y = sample_batch(stream, jax.random.PRNGKey(7))
    batch = {"x": x, "y": y}
    lr = 2e-2

    fused_fn, fused_init = engine.build(fns, engine.EngineConfig(
        backend="fused-pallas", lr=lr, optimizer="sgd", tile_batch=128,
        interpret=True, donate=False))
    float_fn, float_init = engine.build(fns, engine.EngineConfig(
        backend="float", lr=lr, optimizer="sgd", max_grad_norm=None,
        donate=False))

    state_k, _ = fused_fn(fused_init(key), batch)
    state_r, _ = float_fn(float_init(key), batch)
    _params_equal(state_k.params, state_r.params, atol=1e-5)
    assert int(state_k.step) == int(state_r.step) == 1


def test_fused_engine_adam_step_matches_float_reference():
    """One fused-pallas engine step with optimizer='adam' (tile_batch=128 ->
    a single tile = one Adam update on the full minibatch) must match the
    float backend's Adam step: params, both moment stacks, and the step
    counter — the in-kernel Adam is the same rule, just resident in VMEM."""
    cfg = get_smoke("mrf-fpga")
    fns = registry.build(cfg)
    key = jax.random.PRNGKey(0)
    stream = MRFSampleStream(seq=default_sequence(cfg.mrf_n_frames),
                             batch_size=128)
    x, y = sample_batch(stream, jax.random.PRNGKey(7))
    batch = {"x": x, "y": y}
    lr = 1e-3

    fused_fn, fused_init = engine.build(fns, engine.EngineConfig(
        backend="fused-pallas", lr=lr, optimizer="adam", tile_batch=128,
        interpret=True, donate=False))
    float_fn, float_init = engine.build(fns, engine.EngineConfig(
        backend="float", lr=lr, optimizer="adam", max_grad_norm=None,
        donate=False))

    state_k, _ = fused_fn(fused_init(key), batch)
    state_r, _ = float_fn(float_init(key), batch)
    _params_equal(state_k.params, state_r.params, atol=1e-5)
    _params_equal(state_k.opt_state.mu, state_r.opt_state.mu, atol=1e-5)
    _params_equal(state_k.opt_state.nu, state_r.opt_state.nu, atol=1e-7)
    assert int(state_k.opt_state.step) == int(state_r.opt_state.step) == 1
    assert int(state_k.step) == 1


def test_engine_rejects_configs_fused_cannot_honor():
    """The fused path computes grads+update in-kernel: configs it cannot
    honor must fail loudly at build time, never train the wrong rule."""
    from repro.kernels.fused_train.ops import make_engine_step
    from repro.train.step import make_train_step

    with pytest.raises(ValueError, match="microbatches"):
        engine.EngineConfig(backend="fused-pallas", microbatches=2)
    with pytest.raises(ValueError, match="grad_compress"):
        engine.EngineConfig(backend="fused-pallas", grad_compress=True)
    with pytest.raises(ValueError, match="optimizer"):
        engine.EngineConfig(optimizer="rmsprop")  # any backend: whitelist
    with pytest.raises(ValueError, match="sgd"):
        make_engine_step(lr=1e-2, optimizer="rmsprop")
    # Mosaic tiles blocks by 8 rows: the compiled kernel refuses per-sample
    # and other unaligned tiles at config time; the interpreter takes them
    with pytest.raises(ValueError, match="multiple of 8"):
        engine.EngineConfig(backend="fused-pallas", tile_batch=1,
                            interpret=False)
    engine.EngineConfig(backend="fused-pallas", tile_batch=1, interpret=True)
    engine.EngineConfig(backend="fused-pallas", tile_batch=24,
                        interpret=False)

    fused = lambda p, o, a, b: (p, o, a, {})
    with pytest.raises(ValueError, match="microbatches"):
        make_train_step(None, sgd(1e-2), fused_step=fused, microbatches=4)
    with pytest.raises(ValueError, match="compress"):
        make_train_step(None, sgd(1e-2), fused_step=fused, grad_compress=True)


def test_fused_tile_adapts_to_awkward_batch():
    """tile_batch is a ceiling: a batch not divisible by it must still run
    (largest dividing tile), not crash on the kernel grid assert."""
    from repro.kernels.fused_train.ops import effective_tile
    assert effective_tile(192, 128) == 96
    assert effective_tile(100, 128) == 100
    assert effective_tile(7, 4) == 1
    # degradation on prime/awkward sizes: fall back toward per-sample tiles
    assert effective_tile(13, 8) == 1       # prime above the ceiling
    assert effective_tile(97, 128) == 97    # prime under the ceiling: 1 tile
    assert effective_tile(254, 128) == 127  # 2*127 -> the big prime factor
    assert effective_tile(96, 36) == 32     # largest divisor <= ceiling
    # compiled: the largest multiple of 8 that divides, or a ValueError
    assert effective_tile(192, 128, interpret=False) == 96
    assert effective_tile(96, 36, interpret=False) == 32
    assert effective_tile(24, 128, interpret=False) == 24
    for batch, tile in ((100, 128), (13, 8), (254, 128), (64, 4)):
        with pytest.raises(ValueError, match="multiple of 8"):
            effective_tile(batch, tile, interpret=False)
    cfg = get_smoke("mrf-fpga")
    fns = registry.build(cfg)
    stream = MRFSampleStream(seq=default_sequence(cfg.mrf_n_frames),
                             batch_size=24)
    x, y = sample_batch(stream, jax.random.PRNGKey(11))
    step_fn, init_state = engine.build(fns, engine.EngineConfig(
        backend="fused-pallas", lr=1e-2, optimizer="sgd", tile_batch=16,
        donate=False))
    new_state, metrics = step_fn(init_state(jax.random.PRNGKey(0)),
                                 {"x": x, "y": y})
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state.step) == 1
    # prime batch: degrades all the way to per-sample streaming and still runs
    stream_p = MRFSampleStream(seq=default_sequence(cfg.mrf_n_frames),
                               batch_size=13)
    xp, yp = sample_batch(stream_p, jax.random.PRNGKey(12))
    state_p, metrics_p = step_fn(init_state(jax.random.PRNGKey(0)),
                                 {"x": xp, "y": yp})
    assert np.isfinite(float(metrics_p["loss"]))
    assert int(state_p.step) == 1


def test_fused_multi_tile_is_sequential_sgd():
    """tile_batch < batch: the engine step must equal per-tile sequential SGD
    (the paper's streaming regime), not one big minibatch update."""
    cfg = get_smoke("mrf-fpga")
    fns = registry.build(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    stream = MRFSampleStream(seq=default_sequence(cfg.mrf_n_frames),
                             batch_size=64)
    x, y = sample_batch(stream, jax.random.PRNGKey(9))
    lr = 1e-2

    step_fn, init_state = engine.build(fns, engine.EngineConfig(
        backend="fused-pallas", lr=lr, optimizer="sgd", tile_batch=16,
        donate=False))
    new_state, _ = step_fn(init_state(jax.random.PRNGKey(0)), {"x": x, "y": y})

    opt = sgd(lr)
    p, s = params, opt.init(params)
    for t in range(0, 64, 16):
        g = jax.grad(mrf_net.mse_loss)(p, x[t:t + 16], y[t:t + 16])
        p, s = opt.update(g, s, p)
    _params_equal(new_state.params, p, atol=1e-5)


# --------------------------------------------------------------------------
# the launcher, end to end (checkpointing runner, all three backends)
# --------------------------------------------------------------------------

@pytest.fixture
def restore_cache_dir():
    """The launchers point JAX's persistent compilation cache at the
    checkout; put the process-wide setting back for the next test."""
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_location(env_set, monkeypatch, tmp_path,
                                restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set the cache is JAX's to place (it
    reads the variable itself); without it, one fixed directory at the
    root of the checkout (git ignores it: test_no_tracked_bytecode)."""
    from repro.launch import CHECKOUT_CACHE_DIR, enable_compile_cache
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    enable_compile_cache()
    want = restore_cache_dir if env_set else str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == want
    assert (CHECKOUT_CACHE_DIR.parent / "src" / "repro" / "launch").is_dir()


@pytest.mark.parametrize("backend", ["float", "qat-int8", "fused-pallas"])
def test_launcher_smoke_all_backends(backend, tmp_path, restore_cache_dir):
    from repro.launch.train import main
    rc = main(["--arch", "mrf-fpga", "--smoke", "--steps", "3",
               "--batch", "128", "--backend", backend, "--lr", "1e-3",
               "--ckpt-dir", str(tmp_path / backend), "--ckpt-every", "2"])
    assert rc == 0
    # the runner checkpointed: step-0 safety ckpt + the periodic one
    assert (tmp_path / backend / "LATEST").exists()
    assert (tmp_path / backend / "step_2").exists()

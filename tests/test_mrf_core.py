"""Core MRF substrate tests: physics sanity of the Bloch/EPG simulator, the
paper's cycle model (exact numbers), QAT export equivalence, metrics, data
pipeline determinism, and a short end-to-end training run."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import fpga_cost_model as fcm
from repro.core import metrics, mrf_net, qat
from repro.data.epg import default_sequence, simulate_fingerprints, augment
from repro.data.lm_text import TextPipeline
from repro.data.pipeline import MRFSampleStream, sample_batch

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------------------
# simulator physics
# --------------------------------------------------------------------------

def test_fingerprints_normalised_and_distinct():
    seq = default_sequence(32)
    t1 = jnp.array([500.0, 1000.0, 2000.0])
    t2 = jnp.array([50.0, 100.0, 200.0])
    sig = simulate_fingerprints(seq, t1, t2)
    norms = jnp.linalg.norm(sig, axis=-1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)
    # different tissue -> different fingerprint (the whole premise of MRF)
    c01 = jnp.abs(jnp.vdot(sig[0], sig[1]))
    assert float(c01) < 0.999


def test_augment_preserves_shape_and_adds_noise():
    seq = default_sequence(16)
    sig = simulate_fingerprints(seq, jnp.array([800.0]), jnp.array([80.0]))
    noisy = augment(jax.random.PRNGKey(0), sig, snr_range=(5.0, 5.0))
    assert noisy.shape == sig.shape
    assert float(jnp.linalg.norm(noisy - sig)) > 1e-3


def test_rf_rotation_matches_matrix_oracle():
    """The hand-inlined RF rotation in _bloch_step must equal R_x(a) @ m.

    With r1 = r2 = 0 the relaxation factors are exactly 1, so the carried
    magnetization after one TR is precisely the rotated vector — checked
    against an explicit rotation-matrix oracle for both RF phase signs.
    """
    from repro.data.epg import _bloch_step

    m0 = jnp.array([0.3, -0.5, 0.8], jnp.float32)
    for a, sign in ((0.7, 1.0), (1.3, -1.0), (0.0, 1.0)):
        (m_next, next_sign), sig = _bloch_step(
            (m0, jnp.float32(sign)),
            jnp.array([a, 0.012, 0.0, 0.0], jnp.float32))
        eff = a * sign
        rot = np.array([[1.0, 0.0, 0.0],
                        [0.0, np.cos(eff), np.sin(eff)],
                        [0.0, -np.sin(eff), np.cos(eff)]])
        np.testing.assert_allclose(np.asarray(m_next), rot @ np.asarray(m0),
                                   rtol=1e-6, atol=1e-7)
        # the echo signal is the rotated transverse magnetization
        np.testing.assert_allclose(
            complex(sig), complex((rot @ np.asarray(m0))[0]
                                  + 1j * (rot @ np.asarray(m0))[1]),
            rtol=1e-6, atol=1e-7)
        assert float(next_sign) == -sign  # bSSFP phase alternation


@settings(max_examples=6, deadline=None)
@given(t1=st.floats(300, 3000), t2_frac=st.floats(0.05, 0.5),
       seed=st.integers(0, 2**10))
def test_property_simulator_finite(t1, t2_frac, seed):
    seq = default_sequence(16, seed=seed % 4)
    sig = simulate_fingerprints(seq, jnp.array([t1]), jnp.array([t1 * t2_frac]))
    assert bool(jnp.all(jnp.isfinite(jnp.abs(sig))))


# --------------------------------------------------------------------------
# the paper's cycle model — exact numbers
# --------------------------------------------------------------------------

def test_cycle_model_matches_paper_exactly():
    sizes = mrf_net.layer_sizes(32)  # adapted net
    assert fcm.fwd_cycles(sizes) == 56
    assert fcm.bwd_cycles(sizes) == 104
    assert fcm.train_seconds(sizes, 250_000_000) == 200.0
    assert fcm.paper_eq3_seconds() == 200.0


def test_resource_model_within_band():
    est = fcm.resource_estimate(mrf_net.layer_sizes(32))
    paper = fcm.PAPER["resources_nn"]
    assert abs(est["LUT"] - paper["LUT"]) / paper["LUT"] < 0.25
    assert abs(est["DSP"] - paper["DSP"]) / paper["DSP"] < 0.25


def test_tpu_projection_faster_than_fpga():
    t = fcm.tpu_train_seconds(mrf_net.layer_sizes(32), 250_000_000, chips=1,
                              int8=True)
    assert t["t_total_s"] < fcm.paper_eq3_seconds()


# --------------------------------------------------------------------------
# QAT / metrics
# --------------------------------------------------------------------------

def test_qat_export_close_to_fakequant():
    sizes = mrf_net.layer_sizes(16)
    params = mrf_net.init_params(jax.random.PRNGKey(0), sizes)
    qs = qat.init_qat_state(len(params))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, sizes[0]))
    for _ in range(4):
        _, qs = qat.forward_qat(params, qs, x)
    ints = qat.export_int8(params, qs)
    y_fake, _ = qat.forward_qat(params, qs, x, train=False)
    y_int = qat.int_forward(ints, x)
    np.testing.assert_allclose(y_int, y_fake, atol=1e-5)


def test_metrics_zero_for_perfect_prediction():
    y = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (100, 2))) + 1.0
    m = metrics.table1_metrics(y, y)
    for p in ("T1", "T2"):
        assert m[p]["MAPE_%"] == 0.0 and m[p]["RMSE_ms"] == 0.0


# --------------------------------------------------------------------------
# data pipelines
# --------------------------------------------------------------------------

def test_mrf_stream_deterministic():
    stream = MRFSampleStream(seq=default_sequence(16), batch_size=8)
    x1, y1 = sample_batch(stream, jax.random.PRNGKey(7))
    x2, y2 = sample_batch(stream, jax.random.PRNGKey(7))
    np.testing.assert_array_equal(x1, x2)
    assert bool(jnp.all(y1 <= 1.0)) and bool(jnp.all(y1 > 0.0))


def test_lm_pipeline_seekable_and_host_sharded():
    p = TextPipeline(seq_len=32, batch_size=8)
    a = p.batch_at(5)
    b = p.batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    h0 = TextPipeline(seq_len=32, batch_size=8, n_hosts=2, host=0).batch_at(5)
    h1 = TextPipeline(seq_len=32, batch_size=8, n_hosts=2, host=1).batch_at(5)
    assert h0["tokens"].shape[0] == 4
    assert not np.array_equal(h0["tokens"], h1["tokens"])


# --------------------------------------------------------------------------
# short end-to-end training (the paper's software reference)
# --------------------------------------------------------------------------

def test_training_reduces_loss():
    from repro.core.train_loop import TrainConfig, train
    cfg = TrainConfig(n_frames=16, steps=60, lr=3e-3, batch_size=64,
                      log_every=1000)
    params, _, info = train(cfg, verbose=False)
    # loss after training must beat the first-step loss significantly
    first = info["history"][0][1]
    last = info["history"][-1][1]
    assert last < 0.5 * first

"""The benchmark's own MRF simulator: sequence and training-batch sampler.

A copy, op for op, of what the program's ``data/epg.py`` and
``data/pipeline.sample_batch`` compute, kept here so that the training
reference does not move when a later change rewrites the program's
simulator.  The training cells still time the program's own simulator,
because there it is part of the step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

def default_sequence(n_frames: int, seed: int = 0) -> tuple:
    """(flip angles in rad, TRs in s) of the sinusoidal IR-bSSFP train."""
    rng = np.random.default_rng(seed)
    t = np.arange(n_frames)
    lobes = 10.0 + 60.0 * np.abs(np.sin(np.pi * t / (n_frames / 2.0)))
    fa = np.deg2rad(lobes + rng.uniform(-2.0, 2.0, n_frames))
    tr = (0.012 + 0.003 * np.sin(2 * np.pi * t / max(n_frames, 1))
          + rng.uniform(0, 5e-4, n_frames))
    return tuple(fa.tolist()), tuple(tr.tolist())


def _bloch_step(carry, frame):
    m, sign = carry
    fa, tr, r1, r2 = frame
    a = fa * sign
    ca, sa = jnp.cos(a), jnp.sin(a)
    m = jnp.stack([m[0], ca * m[1] + sa * m[2], -sa * m[1] + ca * m[2]])
    e1a = jnp.exp(-tr * 0.5 * r1)
    e2a = jnp.exp(-tr * 0.5 * r2)
    m_te = jnp.stack([m[0] * e2a, m[1] * e2a, 1.0 + (m[2] - 1.0) * e1a])
    sig = m_te[0] + 1j * m_te[1]
    e1b = jnp.exp(-tr * (1.0 - 0.5) * r1)
    e2b = jnp.exp(-tr * (1.0 - 0.5) * r2)
    m_next = jnp.stack([m_te[0] * e2b, m_te[1] * e2b,
                        1.0 + (m_te[2] - 1.0) * e1b])
    return (m_next, -sign), sig


def _simulate_one(t1_s, t2_s, fas, trs, inv_delay):
    r1 = 1.0 / jnp.maximum(t1_s, 1e-6)
    r2 = 1.0 / jnp.maximum(t2_s, 1e-6)
    e1 = jnp.exp(-inv_delay * r1)
    m0 = jnp.array([0.0, 0.0, 1.0 + (-1.0 - 1.0) * e1])
    frames = jnp.stack([fas, trs, jnp.broadcast_to(r1, fas.shape),
                        jnp.broadcast_to(r2, fas.shape)], axis=1)
    _, sig = jax.lax.scan(_bloch_step, (m0, jnp.float32(1.0)), frames)
    return sig


def simulate(seq: tuple, t1_ms, t2_ms, inv_delay: float = 0.018):
    """L2-normalised complex64 fingerprints (n, frames) of (T1, T2) in ms,
    with the inversion pulse of the sequence."""
    fas = jnp.asarray(seq[0], jnp.float32)
    trs = jnp.asarray(seq[1], jnp.float32)
    sig = jax.vmap(lambda a, b: _simulate_one(a, b, fas, trs, inv_delay))(
        jnp.asarray(t1_ms, jnp.float32) / 1e3,
        jnp.asarray(t2_ms, jnp.float32) / 1e3)
    norm = jnp.linalg.norm(sig, axis=-1, keepdims=True)
    return (sig / jnp.maximum(norm, 1e-12)).astype(jnp.complex64)


def augment(key, sig, snr_range):
    """Random global phase and complex noise at an SNR drawn per signal."""
    k_phase, k_snr, k_noise = jax.random.split(key, 3)
    batch = sig.shape[0]
    phase = jax.random.uniform(k_phase, (batch, 1), minval=0.0,
                               maxval=2 * jnp.pi)
    sig = sig * jnp.exp(1j * phase)
    snr = jax.random.uniform(k_snr, (batch, 1), minval=snr_range[0],
                             maxval=snr_range[1])
    n = sig.shape[-1]
    sigma = 1.0 / (snr * jnp.sqrt(jnp.float32(n)))
    noise = sigma * (jax.random.normal(k_noise, sig.shape)
                     + 1j * jax.random.normal(jax.random.fold_in(k_noise, 1),
                                              sig.shape)) / jnp.sqrt(2.0)
    return (sig + noise).astype(jnp.complex64)


def to_features(sig):
    """Complex fingerprints -> [Re | Im] float32 features."""
    return jnp.concatenate([jnp.real(sig), jnp.imag(sig)],
                           axis=-1).astype(jnp.float32)


@partial(jax.jit, static_argnames=("seq", "batch", "t1_range", "t2_range",
                                   "snr_range"))
def sample_batch(key, *, seq: tuple, batch: int, t1_range: tuple,
                 t2_range: tuple, snr_range: tuple):
    """One training batch (features (B, 2F), normalised targets (B, 2)):
    log-uniform T1 and T2 with T2 <= T1, simulated and augmented."""
    k_t1, k_t2, k_aug = jax.random.split(key, 3)
    lo1, hi1 = t1_range
    lo2, hi2 = t2_range
    t1 = jnp.exp(jax.random.uniform(k_t1, (batch,), minval=jnp.log(lo1),
                                    maxval=jnp.log(hi1)))
    t2 = jnp.exp(jax.random.uniform(k_t2, (batch,), minval=jnp.log(lo2),
                                    maxval=jnp.log(hi2)))
    t2 = jnp.minimum(t2, t1)
    sig = augment(k_aug, simulate(seq, t1, t2), snr_range)
    return to_features(sig), jnp.stack([t1 / hi1, t2 / hi2],
                                       axis=-1).astype(jnp.float32)

"""Streaming MRF training-data pipeline.

The paper trains on 250M simulated signals.  Materialising that is absurd;
the right systems design (and what we ship) is an *infinite, seeded,
on-the-fly* sample stream: each batch draws (T1, T2) from the physiological
prior, simulates fingerprints with the Bloch/EPG recursion, and applies the
SNR/phase augmentations — all inside one jit'd function, double-buffered so
host->device transfer overlaps compute.

For multi-host training the stream is sharded by host: host i draws from a
key folded with its process index, so the global batch is i.i.d. without any
coordination (the standard tf.data-free JAX input pattern).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Iterator

import jax
import jax.numpy as jnp

from repro.data.epg import MRFSequence, augment, to_features

# Physiological brain ranges used by the Barbieri-family MRF papers (ms).
T1_RANGE_MS = (100.0, 4000.0)
T2_RANGE_MS = (10.0, 600.0)


@dataclasses.dataclass(frozen=True)
class MRFSampleStream:
    seq: MRFSequence
    batch_size: int
    snr_range: tuple = (2.0, 50.0)
    t1_range: tuple = T1_RANGE_MS
    t2_range: tuple = T2_RANGE_MS

    @property
    def feature_dim(self) -> int:
        return 2 * self.seq.n_frames


@partial(jax.jit, static_argnames=("stream",))
def sample_batch(stream: MRFSampleStream, key: jax.Array):
    """One training batch: features (B, 2F) and targets (B, 2) in NORMALISED units.

    Targets are (T1/T1_max, T2/T2_max) so the MSE loss weighs both maps; metrics
    un-normalise before computing MAPE/MPE/RMSE (paper reports ms).
    """
    k_t1, k_t2, k_aug = jax.random.split(key, 3)
    b = stream.batch_size
    # Log-uniform draw matches the dictionary-density practice for T1/T2 grids.
    lo1, hi1 = stream.t1_range
    lo2, hi2 = stream.t2_range
    t1 = jnp.exp(jax.random.uniform(k_t1, (b,), minval=jnp.log(lo1), maxval=jnp.log(hi1)))
    t2 = jnp.exp(jax.random.uniform(k_t2, (b,), minval=jnp.log(lo2), maxval=jnp.log(hi2)))
    # Enforce T2 <= T1 (physical constraint in tissue).
    t2 = jnp.minimum(t2, t1)
    from repro.data.epg import simulate_fingerprints  # local import to keep jit graph clean

    sig = simulate_fingerprints(stream.seq, t1, t2)
    sig = augment(k_aug, sig, stream.snr_range)
    x = to_features(sig)
    y = jnp.stack([t1 / hi1, t2 / hi2], axis=-1).astype(jnp.float32)
    return x, y


def make_batch_iterator(stream: MRFSampleStream, seed: int = 0,
                        process_index: int | None = None) -> Iterator:
    """Infinite, host-sharded iterator of (features, targets) device arrays."""
    pidx = jax.process_index() if process_index is None else process_index
    key = jax.random.fold_in(jax.random.PRNGKey(seed), pidx)
    step = 0
    while True:
        yield sample_batch(stream, jax.random.fold_in(key, step))
        step += 1


def batch_at(stream: MRFSampleStream, key: jax.Array, step) -> dict:
    """The seekable sampler itself: ``{"x", "y"}`` batch at a global step.

    ``step`` may be a Python int (host dispatch) or a traced int32 scalar —
    the batch key is ``fold_in(key, step)`` either way, so a chunked train
    loop can synthesize batches *inside* ``lax.scan`` (zero steady-state
    host->device transfers) and draw bit-identical data to the host path.
    ``make_batch_factory`` routes through here so the two can never diverge.
    Its ops carry the name scope ``simulate`` in a compiled program.
    """
    with jax.named_scope("simulate"):
        x, y = sample_batch(stream, jax.random.fold_in(key, step))
    return {"x": x, "y": y}


def make_batch_factory(stream: MRFSampleStream,
                       key: jax.Array) -> Callable[[int], dict]:
    """Seekable deterministic batch factory — the ``ft.runner`` data contract.

    ``factory(step)`` returns the SAME ``{"x", "y"}`` batch for the same step
    every time it is called (the batch key is ``fold_in(key, step)``), so a
    checkpoint-restart replays the stream exactly from the resume step.
    """
    def at(step: int) -> dict:
        return batch_at(stream, key, step)
    return at


def denormalize_targets(y, t1_range: tuple = T1_RANGE_MS,
                        t2_range: tuple = T2_RANGE_MS):
    """Normalised (T1/T1_max, T2/T2_max) targets/predictions -> milliseconds.

    The single place that knows how ``sample_batch`` normalised its targets;
    metrics, the examples, and the serving engine all route through here so a
    changed stream range cannot silently corrupt reconstructed maps.
    ``y``: (..., 2) array-like; returns float32 of the same shape.
    """
    scale = jnp.array([t1_range[1], t2_range[1]], jnp.float32)
    return jnp.asarray(y, jnp.float32) * scale


def host_sharded_key(seed: int = 0, process_index: int | None = None) -> jax.Array:
    """Per-host stream key: host i draws i.i.d. batches without coordination."""
    pidx = jax.process_index() if process_index is None else process_index
    return jax.random.fold_in(jax.random.PRNGKey(seed), pidx)


def make_eval_set(seq: MRFSequence, n: int = 5000, seed: int = 123, snr: float = 20.0):
    """The paper's held-out evaluation: n never-before-seen synthetic signals."""
    stream = MRFSampleStream(seq=seq, batch_size=n, snr_range=(snr, snr))
    return sample_batch(stream, jax.random.PRNGKey(seed))

"""The paper's contribution as a TPU kernel: whole-network fused training
(forward + backprop + optimizer update) inside ``pl.pallas_call``.

FPGA -> TPU mapping (DESIGN.md §2), multi-step regime:

* ALVEO: weights live in BRAM/FF for the **entire training run** — the
  bitstream is configured once, then samples stream past the resident
  network until training ends.  Weight state never crosses the board's
  memory boundary mid-run.
* Here: all layer weights (and, for the Adam variant, both moment stacks)
  live in **VMEM scratch across every step of a launch** — loaded from HBM
  once at grid step 0, updated in place over all K steps x all batch tiles,
  and written back to HBM once at the final grid step (see ``multistep.py``,
  which flattens ``grid=(K * n_tiles,)`` over a pre-staged ``(K*B, PAD)``
  sample stream).  Per-launch weight HBM traffic is 2 transfers regardless
  of K — the single-step kernel in this file is the K=1 special case, where
  chunked dispatch had to re-enter the kernel (and re-stream the weight
  stack through HBM) every step.
* The "16-node semi-parallel block" becomes a 128-lane MXU tile: every layer
  is zero-padded to PAD=128 so each layer's matmul is one aligned MXU op.
  Zero padding is self-preserving through fwd+bwd (zero rows/cols stay zero;
  see tests), so no masking is needed except at the loss.

Grid semantics: TPU grids execute sequentially on a core, which makes the
read-modify-write of the scratch weights across grid steps sound (the same
property the classic Pallas matmul accumulator uses).  That sequencing is
exactly what makes the multi-step flattening legal: tile ``k*n_tiles + j``
always sees the weights as updated by every earlier tile of every earlier
step.

Two update modes:
* ``tile_batch = 1``  -> per-sample streaming SGD, the *faithful* FPGA
  algorithm (one update per training signal) — interpreter only: the
  compiled kernel needs tiles of a multiple of ``TILE_ALIGN`` rows;
* ``tile_batch = T``  -> minibatch update per tile, the MXU-native
  reformulation (beyond-paper optimization; see EXPERIMENTS.md §Perf).

``train_tile`` is the shared per-tile body (forward, masked MSE loss,
hand-derived backward, optimizer callback): the single-step kernel here and
the multi-step kernels in ``multistep.py`` both inline it, which is what
makes a K-step launch bit-identical to K single-step launches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.common import resolve_interpret

PAD = 128  # MXU lane width; every layer is padded to this many nodes.

# Mosaic tiles a VMEM block's last two dims by (8, 128), so a batch tile
# must be a multiple of 8 rows to compile (the interpreter takes any).
TILE_ALIGN = 8

# The per-tile losses (and Adam's per-tile bias corrections) are whole 1-D
# SMEM arrays indexed by ``program_id``: one f32 per tile.  SMEM holds
# 1 MiB, so a launch is capped at this many tiles (Adam's three such
# arrays then take 384 KiB).
MAX_LAUNCH_TILES = 1 << 15
SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def train_tile(x, y, w_s, b_s, h_s, update, *, n_layers: int, out_dim: int,
               qat: bool):
    """One batch tile through the VMEM-resident net: forward, masked MSE
    loss, backward (Eq. 2 of the paper), with the optimizer rule injected as
    ``update(l, dw, db)`` — called once per layer, in backward order, with
    the layer's raw gradients.  Returns the tile loss (f32 scalar).

    Every fused kernel (single-step SGD, multi-step SGD, multi-step Adam)
    runs this exact op sequence per tile, so their per-tile arithmetic is
    bit-identical by construction — only the update rule and the grid
    schedule differ.
    """
    tb = x.shape[0]

    def maybe_fq(w):
        if not qat:
            return w
        # symmetric per-channel int8 fake-quant of the live weights (QAT fwd)
        s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0 + 1e-12
        return jnp.clip(jnp.round(w / s), -127, 127) * s

    # --- forward ------------------------------------------------------------
    h = x
    for l in range(n_layers):
        w_l = maybe_fq(w_s[l])
        z = jnp.dot(h, w_l, preferred_element_type=jnp.float32) + b_s[l][None, :]
        h = z if l == n_layers - 1 else jnp.maximum(z, 0.0)
        if l < n_layers - 1:
            h_s[l] = h  # post-activation, reused as both input and relu-mask in bwd

    # --- loss (MSE over the first out_dim lanes) -----------------------------
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, PAD), 1)
    mask = (lane < out_dim).astype(jnp.float32)
    diff = (h - y) * mask
    denom = jnp.float32(tb * out_dim)
    loss = jnp.sum(diff * diff) / denom

    # --- backward + in-scratch optimizer update ------------------------------
    dz = 2.0 * diff / denom
    for l in range(n_layers - 1, -1, -1):
        h_prev = x if l == 0 else h_s[l - 1]
        # propagate delta *before* updating this layer's weights
        if l > 0:
            w_l = maybe_fq(w_s[l])
            dh = jnp.dot(dz, w_l.T, preferred_element_type=jnp.float32)
            relu_mask = (h_prev > 0.0).astype(jnp.float32)
        dw = jnp.dot(h_prev.T, dz, preferred_element_type=jnp.float32)
        # the bias gradient's column sum runs on the MXU like dw: a reduce
        # compiles to a different summation order depending on the program
        # around it, which broke chunked == stepwise bit-parity
        db = jnp.dot(jnp.ones((TILE_ALIGN, tb), jnp.float32), dz,
                     preferred_element_type=jnp.float32)[0]
        update(l, dw, db)
        if l > 0:
            dz = dh * relu_mask
    return loss


def _sgd_update(w_s, b_s, lr: float):
    """The in-scratch SGD rule for ``train_tile`` (the paper's Eq. 2)."""
    def update(l, dw, db):
        w_s[l] = w_s[l] - lr * dw
        b_s[l] = b_s[l] - lr * db
    return update


def _kernel(x_ref, y_ref, w_in_ref, b_in_ref,            # inputs
            w_out_ref, b_out_ref, loss_ref,               # outputs
            w_s, b_s, h_s,                                # scratch
            *, n_layers: int, out_dim: int, lr: float, n_tiles: int,
            qat: bool):
    i = pl.program_id(0)

    # --- load weights into VMEM scratch once -------------------------------
    @pl.when(i == 0)
    def _init():
        w_s[...] = w_in_ref[...]
        b_s[...] = b_in_ref[...]

    loss_ref[i] = train_tile(
        x_ref[...], y_ref[...], w_s, b_s, h_s, _sgd_update(w_s, b_s, lr),
        n_layers=n_layers, out_dim=out_dim, qat=qat)

    # --- flush updated weights to HBM once ----------------------------------
    @pl.when(i == n_tiles - 1)
    def _flush():
        w_out_ref[...] = w_s[...]
        b_out_ref[...] = b_s[...]


@functools.partial(jax.jit, static_argnames=("n_layers", "out_dim", "lr",
                                             "tile_batch", "qat", "interpret"))
def fused_train_call(x_pad, y_pad, w_pad, b_pad, *, n_layers: int, out_dim: int,
                     lr: float, tile_batch: int, qat: bool = False,
                     interpret: bool | None = None):
    """Run one fused pass over the whole (padded) batch.

    x_pad: (B, PAD) fp32; y_pad: (B, PAD) fp32; w_pad: (L, PAD, PAD);
    b_pad: (L, PAD).  B must be a multiple of tile_batch.
    Returns (w_new, b_new, per_tile_losses (B//tile_batch,)).
    ``interpret=None`` auto-detects: compiled on TPU, interpreter elsewhere.

    Over K steps' batches staged back to back this is the multi-step launch
    (``multistep.fused_train_multistep_call``): the grid runs the tiles in
    order, so weights stay resident in VMEM across every step.
    """
    interpret = resolve_interpret(interpret)
    batch, _ = x_pad.shape
    assert batch % tile_batch == 0, (batch, tile_batch)
    n_tiles = batch // tile_batch
    kern = functools.partial(_kernel, n_layers=n_layers, out_dim=out_dim,
                             lr=lr, n_tiles=n_tiles, qat=qat)
    w_new, b_new, losses = pl.pallas_call(
        kern,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile_batch, PAD), lambda i: (i, 0)),   # x tile
            pl.BlockSpec((tile_batch, PAD), lambda i: (i, 0)),   # y tile
            pl.BlockSpec((n_layers, PAD, PAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers, PAD), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n_layers, PAD, PAD), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers, PAD), lambda i: (0, 0)),
            SMEM_SPEC,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_layers, PAD, PAD), jnp.float32),
            jax.ShapeDtypeStruct((n_layers, PAD), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_layers, PAD, PAD), jnp.float32),       # weights
            pltpu.VMEM((n_layers, PAD), jnp.float32),            # biases
            pltpu.VMEM((max(n_layers - 1, 1), tile_batch, PAD), jnp.float32),
        ],
        interpret=interpret,
    )(x_pad, y_pad, w_pad, b_pad)
    return w_new, b_new, losses

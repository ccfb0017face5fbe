"""Multi-step fused training: K train steps per kernel launch, weights (and
Adam moments) resident in VMEM across **all steps** — the true analogue of
the paper's on-FPGA training loop, where the network lives in BRAM for the
whole run and only samples stream past.

The single-step kernel (``kernel.py``) already keeps weights in VMEM across
the batch tiles *of one step*, but chunked dispatch re-entered the
``pallas_call`` every scan iteration: each of the K steps re-loaded and
re-flushed the full ``(L, PAD, PAD)`` weight stack through HBM (2K stack
transfers per chunk) and re-paid the padding/unpadding of the param pytree.
Here the grid flattens to ``(K * n_tiles,)`` over a pre-staged ``(K*B, PAD)``
sample stream: weights load at grid step 0, update in place across every
tile of every step, and flush once at the end — 2 stack transfers per chunk,
one Python dispatch, no scan re-entry.  TPU grids execute sequentially on a
core, so tile ``k*n_tiles + j`` sees the weights exactly as K single-step
launches would have left them: a K-step launch is **bit-identical** to K
sequential ``fused_train_call`` invocations (both inline
``kernel.train_tile``, so the per-tile arithmetic is the same ops in the
same order).

Two optimizer rules, selected statically:

* **SGD** (``fused_train_multistep_call``) — the paper's FPGA training rule,
  reusing the single-step kernel body over the longer flattened grid.
* **Adam** (``fused_train_adam_call``) — the paper's *software* baseline,
  now in-kernel: first/second moment stacks ride as extra input/output refs
  plus VMEM scratch (same residency as the weights), and the bias
  corrections come from the traced global Adam step ``step0``, with
  ``t = step0 + tile_index + 1`` — each batch tile is one Adam update, the
  sequential-update regime the SGD kernel already uses.  The wrapper
  computes them per tile and the kernel reads them from SMEM.
  The update formula mirrors ``optim.optimizers.adam`` op for op, so given
  the same gradients it produces the same bits as the engine's software
  Adam on the padded math (zero-padded lanes have g = m = v = 0 and stay
  exactly zero through the update).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.common import resolve_interpret
from repro.kernels.fused_train.kernel import (PAD, SMEM_SPEC,
                                              fused_train_call, train_tile)

# Adam defaults — must match optim.optimizers.adam for the engine's
# fused path to be interchangeable with the software optimizer.
_ADAM_B1 = 0.9
_ADAM_B2 = 0.999
_ADAM_EPS = 1e-8


# K steps of in-kernel SGD in one launch is the single-step kernel over the
# K steps' batches staged back to back: x_pad/y_pad are ``(K*B, PAD)``, the
# tile must divide the per-step batch B so no tile straddles a step
# (``ops.effective_tile`` picks it), and the ``(K*B//tile_batch,)`` per-tile
# losses regroup into the ``(K, n_tiles)`` per-step trace.
fused_train_multistep_call = fused_train_call


def _adam_kernel(c1_ref, c2_ref,                          # SMEM, per tile
                 x_ref, y_ref, w_in_ref, b_in_ref,        # inputs
                 mw_in_ref, mb_in_ref, vw_in_ref, vb_in_ref,
                 w_out_ref, b_out_ref,                    # outputs
                 mw_out_ref, mb_out_ref, vw_out_ref, vb_out_ref, loss_ref,
                 w_s, b_s, mw_s, mb_s, vw_s, vb_s, h_s,   # scratch
                 *, n_layers: int, out_dim: int, lr: float, b1: float,
                 b2: float, eps: float, weight_decay: float, n_tiles: int,
                 qat: bool):
    i = pl.program_id(0)

    # --- load weights AND both moment stacks into VMEM scratch once ---------
    @pl.when(i == 0)
    def _init():
        w_s[...] = w_in_ref[...]
        b_s[...] = b_in_ref[...]
        mw_s[...] = mw_in_ref[...]
        mb_s[...] = mb_in_ref[...]
        vw_s[...] = vw_in_ref[...]
        vb_s[...] = vb_in_ref[...]

    c1 = c1_ref[i]
    c2 = c2_ref[i]

    def update(l, dw, db):
        # mirrors optim.optimizers.adam.upd op for op — including the
        # weight_decay term at its default 0.0, because dropping the
        # `+ 0.0 * p` changes XLA's fusion choices and costs a ulp of
        # bit-parity with the software optimizer
        for p_s, m_s, v_s, g in ((w_s, mw_s, vw_s, dw), (b_s, mb_s, vb_s, db)):
            m = b1 * m_s[l] + (1 - b1) * g
            v = b2 * v_s[l] + (1 - b2) * jnp.square(g)
            mhat = m / c1
            vhat = v / c2
            step_ = lr * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p_s[l])
            p_s[l] = p_s[l] - step_
            m_s[l] = m
            v_s[l] = v

    loss_ref[i] = train_tile(
        x_ref[...], y_ref[...], w_s, b_s, h_s, update,
        n_layers=n_layers, out_dim=out_dim, qat=qat)

    # --- flush weights + moments to HBM once ---------------------------------
    @pl.when(i == n_tiles - 1)
    def _flush():
        w_out_ref[...] = w_s[...]
        b_out_ref[...] = b_s[...]
        mw_out_ref[...] = mw_s[...]
        mb_out_ref[...] = mb_s[...]
        vw_out_ref[...] = vw_s[...]
        vb_out_ref[...] = vb_s[...]


@functools.partial(jax.jit, static_argnames=("n_layers", "out_dim", "lr",
                                             "b1", "b2", "eps", "weight_decay",
                                             "tile_batch", "qat", "interpret"))
def fused_train_adam_call(step0, x_pad, y_pad, w_pad, b_pad, mw_pad, mb_pad,
                          vw_pad, vb_pad, *, n_layers: int, out_dim: int,
                          lr: float, b1: float = _ADAM_B1, b2: float = _ADAM_B2,
                          eps: float = _ADAM_EPS, weight_decay: float = 0.0,
                          tile_batch: int, qat: bool = False,
                          interpret: bool | None = None):
    """K steps of in-kernel Adam in one launch: weights and both moment
    stacks VMEM-resident throughout.

    ``step0``: int32 scalar — the Adam step counter *before* this launch
    (traced, so chunk dispatches never recompile as the run advances).
    ``mw/mb/vw/vb``: first/second-moment stacks, padded exactly like the
    weights.  Returns ``(w, b, mw, mb, vw, vb, per_tile_losses)``.
    """
    interpret = resolve_interpret(interpret)
    total, _ = x_pad.shape
    assert total % tile_batch == 0, (total, tile_batch)
    n_tiles = total // tile_batch
    # bias corrections, one pair per tile: tile i is Adam update
    # step0 + i + 1, the counter optim.optimizers.adam would have reached,
    # and c1/c2 use its expression.  Computed here, in XLA, because Mosaic
    # cannot lower a scalar powf.
    t = (step0 + 1 + jnp.arange(n_tiles, dtype=jnp.int32)).astype(jnp.float32)
    c1 = 1.0 - jnp.power(b1, t)
    c2 = 1.0 - jnp.power(b2, t)
    kern = functools.partial(_adam_kernel, n_layers=n_layers, out_dim=out_dim,
                             lr=lr, b1=b1, b2=b2, eps=eps,
                             weight_decay=weight_decay, n_tiles=n_tiles,
                             qat=qat)
    stack3 = pl.BlockSpec((n_layers, PAD, PAD), lambda i: (0, 0, 0))
    stack2 = pl.BlockSpec((n_layers, PAD), lambda i: (0, 0))
    outs = pl.pallas_call(
        kern,
        grid=(n_tiles,),
        in_specs=[
            SMEM_SPEC, SMEM_SPEC,                                 # c1, c2
            pl.BlockSpec((tile_batch, PAD), lambda i: (i, 0)),   # x tile
            pl.BlockSpec((tile_batch, PAD), lambda i: (i, 0)),   # y tile
            stack3, stack2,                                       # w, b
            stack3, stack2,                                       # mu
            stack3, stack2,                                       # nu
        ],
        out_specs=[
            stack3, stack2,                                       # w, b
            stack3, stack2,                                       # mu
            stack3, stack2,                                       # nu
            SMEM_SPEC,                                            # losses
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_layers, PAD, PAD), jnp.float32),
            jax.ShapeDtypeStruct((n_layers, PAD), jnp.float32),
            jax.ShapeDtypeStruct((n_layers, PAD, PAD), jnp.float32),
            jax.ShapeDtypeStruct((n_layers, PAD), jnp.float32),
            jax.ShapeDtypeStruct((n_layers, PAD, PAD), jnp.float32),
            jax.ShapeDtypeStruct((n_layers, PAD), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_layers, PAD, PAD), jnp.float32),       # weights
            pltpu.VMEM((n_layers, PAD), jnp.float32),            # biases
            pltpu.VMEM((n_layers, PAD, PAD), jnp.float32),       # mu (w)
            pltpu.VMEM((n_layers, PAD), jnp.float32),            # mu (b)
            pltpu.VMEM((n_layers, PAD, PAD), jnp.float32),       # nu (w)
            pltpu.VMEM((n_layers, PAD), jnp.float32),            # nu (b)
            pltpu.VMEM((max(n_layers - 1, 1), tile_batch, PAD), jnp.float32),
        ],
        interpret=interpret,
    )(c1, c2, x_pad, y_pad, w_pad, b_pad, mw_pad, mb_pad, vw_pad, vb_pad)
    return tuple(outs)

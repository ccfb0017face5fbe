"""Public wrapper for the fused training kernel: pads the MRF net's ragged
layer list to the kernel's uniform (L, 128, 128) layout, runs the kernel, and
unpads back to the param pytree.

The zero padding is *self-preserving*: padded weight rows/cols and biases are
zero, padded activations stay exactly 0 through ReLU, and every padded
gradient entry is a product with one of those zeros — so the unpadded result
equals the unpadded math (asserted against ref.py in the tests).

Padding and unpadding carry the name scope ``stage`` in a compiled program,
as the engine's staging of a chunk's batches does, so that device time
outside the kernel can be told apart from the batches' synthesis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.fused_train.kernel import (MAX_LAUNCH_TILES, PAD, TILE_ALIGN,
                                              fused_train_call)
from repro.kernels.fused_train.multistep import (fused_train_adam_call,
                                                fused_train_multistep_call)
from repro.optim.optimizers import AdamState

# Optimizer rules the fused kernels implement in-VMEM.  Anything else must
# use a stepwise backend (the kernel would silently train with the wrong
# rule otherwise).
FUSED_OPTIMIZERS = ("sgd", "adam")


@jax.named_scope("stage")
def pad_params(params):
    """Ragged [{'w','b'}] -> ((L,PAD,PAD), (L,PAD)) zero-padded stacks."""
    n_layers = len(params)
    w = jnp.zeros((n_layers, PAD, PAD), jnp.float32)
    b = jnp.zeros((n_layers, PAD), jnp.float32)
    for l, layer in enumerate(params):
        i, o = layer["w"].shape
        assert i <= PAD and o <= PAD, f"layer {l} ({i}x{o}) exceeds PAD={PAD}"
        w = w.at[l, :i, :o].set(layer["w"].astype(jnp.float32))
        b = b.at[l, :o].set(layer["b"].astype(jnp.float32))
    return w, b


@jax.named_scope("stage")
def unpad_params(w_pad, b_pad, like):
    out = []
    for l, layer in enumerate(like):
        i, o = layer["w"].shape
        out.append({"w": w_pad[l, :i, :o], "b": b_pad[l, :o]})
    return out


@jax.named_scope("stage")
def _pad_features(a):
    """(rows, d) -> (rows, PAD), zero-padded: the kernel's sample layout."""
    return jnp.zeros((a.shape[0], PAD), jnp.float32).at[:, :a.shape[1]].set(a)


def fused_train_step(params, x, y, *, lr: float, tile_batch: int = 128,
                     qat: bool = False, interpret: bool | None = None):
    """One fused pass over batch (B, D_in)/(B, out): streams tiles through the
    VMEM-resident net.  Returns (new_params, per-tile losses)."""
    batch, d_in = x.shape
    out_dim = y.shape[-1]
    assert d_in <= PAD, f"feature dim {d_in} > PAD={PAD}"
    assert batch % tile_batch == 0, (batch, tile_batch)
    x_pad, y_pad = _pad_features(x), _pad_features(y)
    w_pad, b_pad = pad_params(params)
    w_new, b_new, losses = fused_train_call(
        x_pad, y_pad, w_pad, b_pad, n_layers=len(params), out_dim=out_dim,
        lr=lr, tile_batch=tile_batch, qat=qat, interpret=interpret)
    return unpad_params(w_new, b_new, params), losses


def effective_tile(batch: int, tile_batch: int, *,
                   interpret: bool | None = None) -> int:
    """Largest tile <= tile_batch that divides ``batch`` (kernel grid
    constraint).  In the interpreter any divisor does, down to per-sample
    streaming; the compiled kernel needs a multiple of ``TILE_ALIGN``, and
    a batch with no such divisor is refused with ``ValueError``."""
    step = 1 if resolve_interpret(interpret) else TILE_ALIGN
    t = min(tile_batch, batch) // step * step
    while t and batch % t:
        t -= step
    if not t:
        raise ValueError(
            f"the compiled fused-pallas kernel needs a batch tile that is a "
            f"multiple of {TILE_ALIGN} rows, at most tile_batch={tile_batch} "
            f"and dividing the per-step batch {batch}; none exists")
    return t


def fused_train_multistep(params, opt_state, x, y, *, n_steps: int, lr: float,
                          optimizer: str = "sgd", tile_batch: int = 128,
                          qat: bool = False, interpret: bool | None = None):
    """K training steps in **one** kernel launch, weights (and Adam moments)
    VMEM-resident across all of them.

    ``x``/``y``: ``(K*B, d_in)`` / ``(K*B, out_dim)`` — K steps' batches
    pre-staged back to back (step k = rows ``[k*B, (k+1)*B)``).  The tile is
    the largest divisor of the *per-step* batch B not exceeding
    ``tile_batch``, so no tile ever straddles a step boundary and the grid
    flattens cleanly to ``(K * n_tiles,)``.

    ``opt_state``: for ``optimizer="adam"`` an ``optim.optimizers.AdamState``
    (moments padded into kernel stacks, ``step`` advanced by one per tile —
    the kernel performs one Adam update per tile); for ``"sgd"`` any state
    with a ``step`` field (advanced by ``n_steps``) or ``None``.

    Returns ``(new_params, new_opt_state, losses (K, n_tiles))`` — row k is
    step k's per-tile losses, bit-identical to what K sequential
    single-step fused calls would have produced.
    """
    total, d_in = x.shape
    out_dim = y.shape[-1]
    if total % n_steps:
        raise ValueError(f"staged stream of {total} rows is not divisible "
                         f"into n_steps={n_steps} equal batches")
    per_step = total // n_steps
    tile = effective_tile(per_step, tile_batch, interpret=interpret)
    n_tiles = per_step // tile
    if n_steps * n_tiles > MAX_LAUNCH_TILES:
        raise ValueError(
            f"one launch of {n_steps} steps x {n_tiles} tiles exceeds "
            f"MAX_LAUNCH_TILES={MAX_LAUNCH_TILES} (per-tile values live in "
            f"SMEM): use fewer chunk steps or larger tiles")
    assert d_in <= PAD, f"feature dim {d_in} > PAD={PAD}"
    x_pad, y_pad = _pad_features(x), _pad_features(y)
    w_pad, b_pad = pad_params(params)
    if optimizer == "sgd":
        w_new, b_new, tile_losses = fused_train_multistep_call(
            x_pad, y_pad, w_pad, b_pad, n_layers=len(params), out_dim=out_dim,
            lr=lr, tile_batch=tile, qat=qat, interpret=interpret)
        if opt_state is not None and hasattr(opt_state, "step"):
            new_opt = opt_state._replace(step=opt_state.step + n_steps)
        else:
            new_opt = opt_state
    elif optimizer == "adam":
        if not isinstance(opt_state, AdamState):
            raise ValueError(
                f"optimizer='adam' needs an AdamState, got {type(opt_state)!r}"
                " — build it with optim.optimizers.adam(lr).init(params)")
        mw_pad, mb_pad = pad_params(opt_state.mu)
        vw_pad, vb_pad = pad_params(opt_state.nu)
        step0 = opt_state.step.astype(jnp.int32)
        (w_new, b_new, mw_new, mb_new, vw_new, vb_new,
         tile_losses) = fused_train_adam_call(
            step0, x_pad, y_pad, w_pad, b_pad, mw_pad, mb_pad, vw_pad, vb_pad,
            n_layers=len(params), out_dim=out_dim, lr=lr, tile_batch=tile,
            qat=qat, interpret=interpret)
        new_opt = AdamState(step=opt_state.step + n_steps * n_tiles,
                            mu=unpad_params(mw_new, mb_new, params),
                            nu=unpad_params(vw_new, vb_new, params))
    else:
        raise ValueError(
            f"fused backend implements optimizers {FUSED_OPTIMIZERS}, got "
            f"{optimizer!r}; use a stepwise backend for anything else")
    return (unpad_params(w_new, b_new, params), new_opt,
            tile_losses.reshape(n_steps, n_tiles))


def make_engine_step(*, lr: float, optimizer: str = "sgd",
                     tile_batch: int = 128, qat: bool = False,
                     interpret: bool | None = None):
    """The ``fused_step`` backend for ``repro.train.step.make_train_step``.

    Conforms the kernel to the engine contract
    ``(params, opt_state, aux, batch) -> (new_params, new_opt_state,
    new_aux, metrics)``: the whole grads+update pipeline runs inside the
    kernel with the engine's configured rule — in-kernel SGD (the paper's
    FPGA algorithm) or in-kernel Adam (moment stacks resident next to the
    weights).  aux passes through untouched and the metrics carry the mean
    over per-tile losses (each tile sees params already updated by its
    predecessors, the paper's sequential-update regime).

    ``tile_batch`` is a ceiling: the actual tile is the largest divisor of
    the (static) batch size not exceeding it.  Raises ``ValueError`` for an
    optimizer the kernel does not implement — silently training with the
    wrong rule is the one thing this backend must never do.
    """
    if optimizer not in FUSED_OPTIMIZERS:
        raise ValueError(
            f"fused-pallas trains in-kernel and implements only "
            f"{FUSED_OPTIMIZERS}; got optimizer={optimizer!r}. Use "
            f"backend='float' (or another stepwise backend) for it.")

    def fused(params, opt_state, aux, batch):
        new_params, new_opt, losses = fused_train_multistep(
            params, opt_state, batch["x"], batch["y"], n_steps=1, lr=lr,
            optimizer=optimizer, tile_batch=tile_batch, qat=qat,
            interpret=interpret)
        return new_params, new_opt, aux, {"loss": jnp.mean(losses, axis=1)[0]}
    return fused

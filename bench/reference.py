"""Plain float32 training reference for the MRF MLP.

Nothing here imports the program or takes what it made.  Weights come from
the same seed by the same published initialisation (He-uniform, zero
biases); batches come from ``bench.sim``.  The backward pass is written out
by hand so that every matrix product, forward and backward, goes through
``dot`` at one stated precision:

* ``"highest"``: float32 products (``lax.Precision.HIGHEST``);
* ``"high"``: three bfloat16 passes (``hi*hi + hi*lo + lo*hi`` of each
  operand split into a bfloat16 head and tail), summed in float32: XLA's
  ``Precision.HIGH`` on a TPU, written out so that every backend computes
  it alike;
* ``"bfloat16"``: one bfloat16 pass, the operands rounded to bfloat16 by
  ``lax.reduce_precision`` and the products summed in float32: what the
  fused kernel computes at Mosaic's default precision, the program's own
  path below the configuration's, emulated alike on every backend so that
  a CPU test sees it too.

Training follows the fused kernel's update rule: one update per batch tile
of ``tile`` rows, each tile's loss the mean squared error over its rows and
the two outputs, taken before that tile's update.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def init_params(key, sizes):
    """He-uniform weights, zero biases: [{"w": (in, out), "b": (out,)}]."""
    params = []
    for n_in, n_out in zip(sizes[:-1], sizes[1:]):
        key, sub = jax.random.split(key)
        bound = jnp.sqrt(6.0 / n_in)
        w = jax.random.uniform(sub, (n_in, n_out), jnp.float32,
                               minval=-bound, maxval=bound)
        params.append({"w": w, "b": jnp.zeros((n_out,), jnp.float32)})
    return params


def _to_bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def dot(a, b, precision: str):
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.dot(a, b, precision=hp)
    if precision == "high":
        a_hi, b_hi = _to_bf16(a), _to_bf16(b)
        a_lo, b_lo = _to_bf16(a - a_hi), _to_bf16(b - b_hi)
        return (jnp.dot(a_hi, b_hi, precision=hp)
                + jnp.dot(a_hi, b_lo, precision=hp)
                + jnp.dot(a_lo, b_hi, precision=hp))
    if precision == "bfloat16":
        return jnp.dot(_to_bf16(a), _to_bf16(b), precision=hp)
    raise ValueError(f"unknown precision {precision!r}")


def tile_loss_and_grads(params, x, y, precision: str):
    """MSE of one tile and its gradients, by hand-written backprop."""
    hs = [x]
    for i, layer in enumerate(params):
        z = dot(hs[-1], layer["w"], precision) + layer["b"]
        hs.append(z if i == len(params) - 1 else jnp.maximum(z, 0.0))
    diff = hs[-1] - y
    denom = jnp.float32(diff.size)
    loss = jnp.sum(diff * diff) / denom
    dz = 2.0 * diff / denom
    grads = [None] * len(params)
    for i in range(len(params) - 1, -1, -1):
        grads[i] = {"w": dot(hs[i].T, dz, precision),
                    "b": jnp.sum(dz, axis=0)}
        if i:
            dz = dot(dz, params[i]["w"].T, precision) * (hs[i] > 0.0)
    return loss, grads


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return (jnp.int32(0), zeros, jax.tree.map(jnp.zeros_like, params))


@partial(jax.jit, static_argnames=("optimizer", "lr", "tile", "precision",
                                   "keep_rows"))
def train_step(params, opt, x, y, *, optimizer: str, lr: float, tile: int,
               precision: str, keep_rows: int | None = None):
    """One step over batch (x, y), one update per tile.  Returns (params,
    opt, per-tile losses).  ``keep_rows`` keeps only that many rows of each
    tile, the mean taken over them: a planted fault, never the reference."""
    n_tiles = x.shape[0] // tile
    xt = x.reshape(n_tiles, tile, x.shape[1])
    yt = y.reshape(n_tiles, tile, y.shape[1])
    if keep_rows is not None:
        xt, yt = xt[:, :keep_rows], yt[:, :keep_rows]

    def body(carry, xy):
        p, o = carry
        loss, g = tile_loss_and_grads(p, xy[0], xy[1], precision)
        if optimizer == "sgd":
            p = jax.tree.map(lambda a, b: a - lr * b, p, g)
        else:
            t, m, v = o
            t = t + 1
            tf = t.astype(jnp.float32)
            c1 = 1.0 - jnp.power(ADAM_B1, tf)
            c2 = 1.0 - jnp.power(ADAM_B2, tf)
            m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b,
                             m, g)
            v = jax.tree.map(
                lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * jnp.square(b), v, g)
            p = jax.tree.map(
                lambda q, a, b: q - lr * ((a / c1) / (jnp.sqrt(b / c2)
                                                      + ADAM_EPS)), p, m, v)
            o = (t, m, v)
        return (p, o), loss

    (params, opt), losses = jax.lax.scan(body, (params, opt), (xt, yt))
    return params, opt, losses

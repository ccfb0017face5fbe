"""One training engine for the MRF nets — stepwise or chunked dispatch.

The repo used to train the MRF net through three disjoint hand-rolled loops
(core/train_loop for float/QAT, examples/mrf_fpga_train for the fused Pallas
kernel, and the production train stack the MRF net couldn't reach).  This
module folds them into the single ``repro.train`` engine: every backend
produces the same ``(TrainState, batch) -> (TrainState, metrics)`` step and
runs under ``ft.runner`` — gaining checkpoint/restart, the straggler
watchdog, and seekable deterministic data replay.

Backends
--------
``float``        value_and_grad on the fp32 MSE loss -> Adam/SGD (the paper's
                 software setup).
``qat-int8``     fake-quant forward with EMA activation observers; the
                 observer state rides in ``TrainState.aux`` so it checkpoints
                 and restores with the params (Jacob et al. 2017 QAT).
``fused-pallas`` the on-accelerator whole-step kernel
                 (kernels/fused_train): forward + backprop + optimizer
                 update (in-kernel SGD or Adam, per ``cfg.optimizer``)
                 inside one pallas_call, the paper's actual contribution.

Chunked execution
-----------------
For the <30k-param MRF net the per-step device work is microseconds, so the
stepwise loop is dispatch-bound: one Python dispatch (and, with a metrics
callback, one blocking host sync) per step.  ``chunk_steps > 1`` switches
the engine to chunked dispatch: ``lax.scan`` over ``chunk_steps`` train
steps inside one jitted, state-donating call, with batches synthesized
*inside* the scan by folding the global step index into the stream key
(``data/pipeline.batch_at`` — the same sampler the stepwise factory uses,
so both paths draw identical batches and the seekable-by-step restart
contract is preserved).  The fused-pallas backend goes one further: a chunk
is **one multi-step kernel launch** with weights (and Adam moments) resident
in VMEM across all ``chunk_steps`` steps — no scan, no kernel re-entry, 2
weight-stack HBM transfers per chunk instead of ``2*chunk_steps``
(kernels/fused_train/multistep.py).  Per-step metrics come back stacked and are fetched
once per chunk, asynchronously (the runner dispatches chunk N+1 before
syncing chunk N's metrics).  Chunked is **bit-identical** to stepwise for
every backend — same final ``TrainState``, same per-step losses — making it
a pure performance change (guarded by tests/test_chunked_training.py).

``build(fns, cfg)`` returns ``(step_fn, init_state)``;
``build_chunked(fns, cfg, stream, data_key)`` returns the chunk dispatcher
``chunk_fn(state, start, n)``; ``train(...)`` is the one-call path the thin
wrappers (core/train_loop, examples, benchmarks) use and selects the mode
from ``cfg.chunk_steps``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.data.epg import default_sequence
from repro.data.pipeline import MRFSampleStream, batch_at, make_batch_factory
from repro.ft.checkpoint import latest_step
from repro.ft.runner import RunnerConfig, run
from repro.kernels.common import resolve_interpret
from repro.kernels.fused_train import ops as fused_ops
from repro.models import mrf as mrf_model
from repro.models.lm import ModelFns
from repro.optim import adam, sgd
from repro.train.step import (TrainState, init_train_state, make_chunked_step,
                              make_train_step)

BACKENDS = ("float", "qat-int8", "fused-pallas")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    backend: str = "float"
    lr: float = 1e-4
    optimizer: str = "adam"       # paper: Adam in software, SGD on the FPGA
    microbatches: int = 1
    max_grad_norm: float | None = None  # None = no clipping (paper setup)
    grad_compress: bool = False
    # fused-pallas knobs: tile_batch=1 is the paper-faithful per-sample SGD
    # stream (interpreter only: compiled tiles are multiples of 8); 128 is
    # the MXU-native minibatch mode.  interpret=None auto-detects: the
    # compiled kernel on TPU, interpreter elsewhere.
    tile_batch: int = 128
    interpret: bool | None = None
    donate: bool = True
    # chunk_steps=1 is the stepwise loop; >1 dispatches lax.scan chunks with
    # in-scan batch synthesis (bit-identical, dispatch-bound loops only pay
    # one Python dispatch + one async metrics fetch per chunk).
    chunk_steps: int = 1

    def __post_init__(self):
        assert self.backend in BACKENDS, (self.backend, BACKENDS)
        assert self.chunk_steps >= 1, self.chunk_steps
        if self.optimizer not in ("adam", "sgd"):
            raise ValueError(f"optimizer must be one of ('adam', 'sgd'), got "
                             f"{self.optimizer!r}")
        if self.backend == "fused-pallas":
            # the kernel computes grads AND the update in-VMEM: there is no
            # grad pytree to accumulate or compress, so these knobs would be
            # silent lies — refuse loudly instead of training the wrong thing
            if self.microbatches != 1:
                raise ValueError(
                    f"fused-pallas computes the update in-kernel: "
                    f"microbatches={self.microbatches} cannot be honored")
            if self.grad_compress:
                raise ValueError("fused-pallas computes the update in-kernel:"
                                 " grad_compress cannot be honored")
            if self.optimizer not in fused_ops.FUSED_OPTIMIZERS:
                raise ValueError(
                    f"fused-pallas implements optimizers "
                    f"{fused_ops.FUSED_OPTIMIZERS} in-kernel, got "
                    f"{self.optimizer!r}")
            if (self.tile_batch % fused_ops.TILE_ALIGN
                    and not resolve_interpret(self.interpret)):
                raise ValueError(
                    f"the compiled fused-pallas kernel needs tile_batch to "
                    f"be a multiple of {fused_ops.TILE_ALIGN}, got "
                    f"{self.tile_batch} (per-sample tiles run only in the "
                    f"interpreter)")


def _backend_step(fns: ModelFns, cfg: EngineConfig, opt):
    """(un-jitted ``(state, batch) -> (state, metrics)`` step, aux factory)
    for ``cfg.backend`` — the shared core of ``build`` and ``build_chunked``,
    so stepwise and chunked run literally the same step function."""
    if cfg.backend == "fused-pallas":
        # the configured rule (SGD or Adam) lives inside the kernel; ``opt``
        # shapes the optimizer slots (incl. Adam moment stacks) so the
        # TrainState pytree is backend-uniform — the kernel reads and writes
        # those slots through make_engine_step's padding.
        step = make_train_step(
            None, opt,
            fused_step=fused_ops.make_engine_step(
                lr=cfg.lr, optimizer=cfg.optimizer,
                tile_batch=cfg.tile_batch, interpret=cfg.interpret))
        aux_of = lambda params: None
    elif cfg.backend == "qat-int8":
        step = make_train_step(
            mrf_model.qat_loss, opt, microbatches=cfg.microbatches,
            max_grad_norm=cfg.max_grad_norm, grad_compress=cfg.grad_compress,
            aux_loss=True)
        aux_of = mrf_model.init_qat_aux
    else:
        step = make_train_step(
            fns.loss, opt, microbatches=cfg.microbatches,
            max_grad_norm=cfg.max_grad_norm, grad_compress=cfg.grad_compress)
        aux_of = lambda params: None
    return step, aux_of


def _make_init(fns: ModelFns, cfg: EngineConfig, opt, aux_of):
    def init_state(key: jax.Array) -> TrainState:
        params = fns.init(key)
        return init_train_state(params, opt, grad_compress=cfg.grad_compress,
                                aux=aux_of(params))
    return init_state


def build(fns: ModelFns, cfg: EngineConfig
          ) -> tuple[Callable, Callable[[jax.Array], TrainState]]:
    """(jitted step conforming to ``(state, batch) -> (state, metrics)``,
    ``init_state(key) -> TrainState``) for any backend."""
    opt = adam(cfg.lr) if cfg.optimizer == "adam" else sgd(cfg.lr)
    step, aux_of = _backend_step(fns, cfg, opt)
    jit_step = jax.jit(step, donate_argnums=(0,) if cfg.donate else ())
    return jit_step, _make_init(fns, cfg, opt, aux_of)


def _make_fused_chunk(cfg: EngineConfig, stream: MRFSampleStream,
                      data_key: jax.Array):
    """``chunk_fn(state, start, n)`` for the fused backend: ``n`` steps =
    **one multi-step kernel launch** with weights (and Adam moments) resident
    in VMEM across all of them (kernels/fused_train/multistep.py) — where
    stepwise backends fold ``n`` steps into a ``lax.scan``, the fused backend
    doesn't even re-enter the kernel.

    Batches are pre-staged into one ``(n*B, ...)`` stream by the same
    ``batch_at(stream, data_key, start + k)`` contract the scan path uses
    (``n`` is static, so the Python staging loop traces once per chunk
    length and the seekable-by-step restart semantics survive unchanged).
    Per-step metrics come back as the kernel's ``(n,)`` loss trace —
    element-identical to ``n`` stepwise fused calls.  The staging ops carry
    the name scope ``stage``, the batches' own ``simulate``.
    """
    def chunk_step(state: TrainState, start, n: int):
        staged = [batch_at(stream, data_key, start + k) for k in range(n)]
        with jax.named_scope("stage"):
            x = jnp.concatenate([b["x"] for b in staged])
            y = jnp.concatenate([b["y"] for b in staged])
        new_params, new_opt, losses = fused_ops.fused_train_multistep(
            state.params, state.opt_state, x, y, n_steps=n, lr=cfg.lr,
            optimizer=cfg.optimizer, tile_batch=cfg.tile_batch,
            interpret=cfg.interpret)
        new_state = TrainState(step=state.step + n, params=new_params,
                               opt_state=new_opt,
                               ef_residual=state.ef_residual, aux=state.aux)
        return new_state, {"loss": jnp.mean(losses, axis=1)}
    return chunk_step


def build_chunked(fns: ModelFns, cfg: EngineConfig, stream: MRFSampleStream,
                  data_key: jax.Array
                  ) -> tuple[Callable, Callable[[jax.Array], TrainState]]:
    """(jitted ``chunk_fn(state, start, n) -> (state, stacked_metrics)``,
    ``init_state``) — the chunked dispatcher for any backend.

    Stepwise backends run ``n`` steps inside one ``lax.scan``; the fused
    backend dispatches the multi-step kernel instead (one launch, weights
    VMEM-resident across all ``n`` steps — see ``_make_fused_chunk``).
    Either way batches are synthesized on-device from
    ``batch_at(stream, data_key, start + i)`` so the chunk draws exactly the
    batches the stepwise factory would.  ``n`` is static (the final ragged
    chunk compiles once at its own length); ``start`` is a traced scalar, so
    chunk dispatches never recompile as the run advances.
    """
    opt = adam(cfg.lr) if cfg.optimizer == "adam" else sgd(cfg.lr)
    step, aux_of = _backend_step(fns, cfg, opt)
    if cfg.backend == "fused-pallas":
        chunk = _make_fused_chunk(cfg, stream, data_key)
    else:
        chunk = make_chunked_step(step, lambda s: batch_at(stream, data_key, s))
    jit_chunk = jax.jit(chunk, static_argnums=(2,),
                        donate_argnums=(0,) if cfg.donate else ())
    return jit_chunk, _make_init(fns, cfg, opt, aux_of)


def default_stream(model_cfg, batch_size: int) -> MRFSampleStream:
    return MRFSampleStream(seq=default_sequence(model_cfg.mrf_n_frames),
                           batch_size=batch_size)


def train(fns: ModelFns, engine_cfg: EngineConfig, runner_cfg: RunnerConfig,  # jaxlint: disable=SHARD -- delegates to step.make_train_step; placement via explicit `shardings` arg
          *, batches: Callable[[int], Any] | None = None,
          stream: MRFSampleStream | None = None,
          data_key: jax.Array | None = None, init_key: jax.Array | None = None,
          batch_size: int = 256, shardings=None, on_metrics=None):
    """Train an MRF net end to end through ``ft.runner``.

    Returns ``(state, step, info)`` where info carries wall-clock seconds and
    the samples/s throughput.  ``batches`` (a seekable ``step -> batch``
    factory) overrides the default stream+key construction — stepwise mode
    only: chunked runs synthesize batches on-device and need the
    ``stream``/``data_key`` pair itself.
    """
    chunked = engine_cfg.chunk_steps > 1
    if chunked and batches is not None:
        raise ValueError(
            "chunk_steps > 1 synthesizes batches on-device inside the scan: "
            "pass the (stream, data_key) pair instead of a host batches "
            "factory, so the data source is unambiguous and the chunked and "
            "stepwise paths draw identical batches")
    def stream_and_key():
        return (stream if stream is not None
                else default_stream(fns.cfg, batch_size),
                data_key if data_key is not None else jax.random.PRNGKey(1))

    if chunked:
        stream, data_key = stream_and_key()
        step_fn = None  # the chunked runner never consults the stepwise path
        chunk_fn, init_state = build_chunked(fns, engine_cfg, stream, data_key)
        batch_size = stream.batch_size
    else:
        chunk_fn = None
        step_fn, init_state = build(fns, engine_cfg)
        if batches is None:
            stream, data_key = stream_and_key()
            batches = make_batch_factory(stream, data_key)
            batch_size = stream.batch_size
    if engine_cfg.backend == "fused-pallas" and stream is not None:
        # refuse a batch the compiled kernel cannot tile before the runner
        # writes its first checkpoint
        fused_ops.effective_tile(stream.batch_size, engine_cfg.tile_batch,
                                 interpret=engine_cfg.interpret)
    state0 = init_state(init_key if init_key is not None
                        else jax.random.PRNGKey(0))

    resume0 = latest_step(runner_cfg.ckpt_dir) or 0
    executed = 0  # steps run THIS invocation (a resume skips earlier ones)

    count_metrics = None
    if on_metrics is not None:
        def count_metrics(step, metrics, dt):
            nonlocal executed
            executed += 1
            on_metrics(step, metrics, dt)

    t0 = time.perf_counter()
    state, step = run(step_fn, state0, batches, runner_cfg,
                      shardings=shardings, on_metrics=count_metrics,
                      chunk_fn=chunk_fn, chunk_steps=engine_cfg.chunk_steps)
    wall = time.perf_counter() - t0
    if on_metrics is None:
        # no callback -> the runner skipped per-step syncs and we never saw
        # per-step ticks; progress-from-resume is the executed count.  Note
        # this omits steps re-executed after a mid-run crash/restart (wall
        # still includes them) — register a callback for exact throughput
        # accounting under fault injection.
        executed = step - resume0
    info = {"wall_seconds": wall, "steps_executed": executed,
            "samples_per_s": executed * batch_size / max(wall, 1e-9)}
    return state, step, info

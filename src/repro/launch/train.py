"""Training launcher: ``python -m repro.launch.train --arch tinyllama-1.1b
--smoke --steps 200`` or ``--arch mrf-fpga --smoke --backend fused-pallas``.

Composes the full stack: config -> model -> optimizer -> fault-tolerant
runner (checkpoint/restart, straggler watchdog) -> metrics log.  On the CPU
container use ``--smoke`` (reduced same-family config); on a TPU cluster the
same driver runs the full config under ``make_production_mesh()`` with the
logical-axis shardings (pass --mesh single|multi).

The MRF reconstruction nets (``--arch mrf-fpga | mrf-original``) run through
the same runner with the backend selected by ``--backend``:
``float`` / ``qat-int8`` / ``fused-pallas`` (see repro.train.engine).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke
from repro.data.lm_text import TextPipeline
from repro.dist.sharding import use_rules
from repro.ft.runner import RunnerConfig, run
from repro.launch import enable_compile_cache
from repro.models import registry
from repro.models.encdec import enc_len_for
from repro.optim import adam
from repro.train.step import init_train_state, make_train_step


def make_batches(cfg, pipe: TextPipeline):
    def at(step: int):
        batch = pipe.batch_at(step)
        b = batch["tokens"].shape[0]
        if cfg.family == "vlm":
            key = jax.random.PRNGKey(step)
            batch["prefix_embeds"] = 0.02 * jax.random.normal(
                key, (b, cfg.n_prefix_embeds, cfg.d_model), jnp.bfloat16)
            batch["labels"][:, :cfg.n_prefix_embeds] = -1
        if cfg.family == "encdec":
            key = jax.random.PRNGKey(step)
            batch["frames"] = 0.02 * jax.random.normal(
                key, (b, enc_len_for(batch["tokens"].shape[1]), cfg.d_model),
                jnp.bfloat16)
        return batch
    return at


def _metrics_logger(total_steps):
    def log(step, metrics, dt):
        if step % 10 == 0 or step == total_steps:
            gnorm = metrics.get("grad_norm")
            gtxt = "" if gnorm is None else f"gnorm {float(gnorm):.3f} "
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"{gtxt}{dt*1000:.0f} ms", flush=True)
    return log


def _mesh_context(args):
    """(context manager, tp) — nullcontext + tp=1 when running mesh-less."""
    if args.mesh == "none":
        return contextlib.nullcontext(), 1
    from repro.launch.mesh import make_production_mesh, rules_for
    mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    rules = rules_for(mesh, global_batch=args.batch)
    return use_rules(rules), mesh.shape["model"]


def run_mrf(args, cfg) -> int:
    """The MRF nets through the unified engine: one runner, three backends,
    stepwise or chunked dispatch (--chunk-steps)."""
    from repro.core.train_loop import evaluate
    from repro.data.pipeline import host_sharded_key
    from repro.train import engine

    backend = args.backend
    if args.quant == "qat-int8":  # the LM-zoo spelling of the same request
        if backend == "fused-pallas":
            raise SystemExit("--quant qat-int8 conflicts with "
                             "--backend fused-pallas (kernel QAT is a "
                             "different path); drop one of the flags")
        backend = "qat-int8"
    optimizer = args.optimizer or (
        "sgd" if backend == "fused-pallas" else "adam")
    if backend == "fused-pallas":
        if args.microbatches != 1 or args.grad_compress:
            raise SystemExit("--microbatches/--grad-compress have no effect "
                             "with --backend fused-pallas (the update is "
                             "computed in-kernel)")
        # --optimizer adam is fine: the kernel implements Adam in-VMEM with
        # the moment stacks resident next to the weights (multistep.py)

    ckpt_dir = args.ckpt_dir or f"/tmp/repro_ckpt/{cfg.name}-{backend}"
    from repro.ft.checkpoint import latest_step
    resume = latest_step(ckpt_dir)
    if resume:
        print(f"resuming from checkpoint step {resume} in {ckpt_dir}")

    ctx, tp = _mesh_context(args)
    with ctx:
        fns = registry.build(cfg, tp=tp)
        ecfg = engine.EngineConfig(
            backend=backend, lr=args.lr, optimizer=optimizer,
            microbatches=args.microbatches,
            grad_compress=args.grad_compress, tile_batch=args.tile_batch,
            chunk_steps=args.chunk_steps)
        stream = engine.default_stream(cfg, args.batch)
        rcfg = RunnerConfig(total_steps=args.steps, ckpt_dir=ckpt_dir,
                            ckpt_every=args.ckpt_every,
                            inject_fault_at=args.inject_fault_at)
        from repro.configs.base import param_count
        print(f"arch={cfg.name} backend={backend} "
              f"params={param_count(cfg):,} "
              f"tp={tp} chunk_steps={args.chunk_steps}")
        state, step, info = engine.train(
            fns, ecfg, rcfg, stream=stream,
            data_key=host_sharded_key(seed=1), batch_size=args.batch,
            on_metrics=_metrics_logger(args.steps))
    # qat-int8 carries its observers in state.aux: evaluate the fake-quant
    # net the backend actually trained, not the float forward
    m = evaluate(state.params, stream.seq, qstate=state.aux, n=1000)
    print(f"done at step {step}: {info['samples_per_s']:.0f} samples/s; "
          f"T1 MAPE {m['T1']['MAPE_%']:.2f}%  T2 MAPE {m['T2']['MAPE_%']:.2f}%")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--backend", default="float",
                    choices=["float", "qat-int8", "fused-pallas"],
                    help="MRF engine backend (mrf-* archs only)")
    ap.add_argument("--optimizer", default=None, choices=["adam", "sgd"],
                    help="default: adam (sgd for the fused-pallas backend)")
    ap.add_argument("--tile-batch", type=int, default=128,
                    help="fused-pallas batch tile (1 = per-sample SGD)")
    ap.add_argument("--chunk-steps", type=int, default=1,
                    help="train steps per dispatch (mrf-* archs): >1 runs a "
                         "lax.scan chunk with in-scan batch synthesis — "
                         "bit-identical to stepwise, dispatch-bound loops "
                         "run much faster (1 = stepwise, the default)")
    ap.add_argument("--quant", default=None, choices=[None, "qat-int8"],
                    help="the paper's technique: int8 QAT training (LM zoo)")
    ap.add_argument("--grad-compress", action="store_true",
                    help="int8 error-feedback gradient compression")
    ap.add_argument("--ckpt-dir", default=None,
                    help="default: /tmp/repro_ckpt/<arch>[-<backend>] "
                         "(namespaced so runs don't resume each other's "
                         "incompatible state)")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--mesh", choices=["none", "single", "multi"],
                    default="none")
    ap.add_argument("--inject-fault-at", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "mrf":
        return run_mrf(args, cfg)

    if args.quant:
        cfg = dataclasses.replace(cfg, quant=args.quant)
    vocab_cap = min(cfg.vocab_size, 256)

    ctx, tp = _mesh_context(args)
    with ctx:
        fns = registry.build(cfg, tp=tp)
        opt = adam(args.lr)
        step_fn = make_train_step(fns.loss, opt,
                                  microbatches=args.microbatches,
                                  grad_compress=args.grad_compress)
        jit_step = jax.jit(step_fn, donate_argnums=(0,))

        params = fns.init(jax.random.PRNGKey(0))
        state = init_train_state(params, opt, grad_compress=args.grad_compress)
        n_params = sum(p.size for p in jax.tree.leaves(params))
        print(f"arch={cfg.name} params={n_params:,} tp={tp}")

        pipe = TextPipeline(seq_len=args.seq, batch_size=args.batch,
                            vocab_size=vocab_cap)
        batches = make_batches(cfg, pipe)

        ckpt_dir = args.ckpt_dir or f"/tmp/repro_ckpt/{cfg.name}"
        rcfg = RunnerConfig(total_steps=args.steps, ckpt_dir=ckpt_dir,
                            ckpt_every=args.ckpt_every,
                            inject_fault_at=args.inject_fault_at)
        state, step = run(jit_step, state, batches, rcfg,
                          on_metrics=_metrics_logger(args.steps))
    print(f"done at step {step}; final loss above.")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

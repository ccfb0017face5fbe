"""Serving launcher: one driver, two families.

Token families (LM zoo): batched prefill + lockstep decode over a request
pool — ``python -m repro.launch.serve --arch tinyllama-1.1b --smoke``.

MRF reconstruction family: the queued map-reconstruction stack
(``repro.serve.recon`` = queue + wave executor) — ``python -m
repro.launch.serve --arch mrf-fpga --backend int8 --smoke`` trains a QAT net
(or loads ``--artifact``), exports and round-trips the servable int8
artifact, reconstructs a phantom-slice request wave through the bucketed
engine, and cross-checks the int8 path against the ``qat.int_forward``
oracle bit-for-bit.  ``--serve-mode pipelined`` serves the same trace
through the double-buffered executor (``--max-wave-voxels`` /
``--max-wait-ms`` control wave formation) and additionally asserts the
pipelined maps are bit-identical to sync serving.

Chaos smoke: ``--fault-schedule`` (a ``serve.faults`` JSON schedule)
and/or the admission knobs (``--max-pending-voxels``,
``--shed-deadline-ms``) switch the MRF family into the overload/fault
accounting path — enqueue everything, drain through the injected faults,
then assert every ticket landed in exactly one terminal state
(done/failed/shed) and that every served map is bit-identical to healthy
serving.  ``--expect-shed`` / ``--expect-degraded`` make the smoke fail
unless load shedding / the fused->lax circuit breaker actually engaged,
so CI proves the machinery fired rather than trivially passing.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke
from repro.launch import enable_compile_cache
from repro.models import registry
from repro.models.encdec import enc_len_for
from repro.serve.decode import make_prefill_step, make_serve_step


def run_token_serve(args, cfg) -> int:
    """Batched prefill + decode for the token-generating families."""
    fns = registry.build(cfg, tp=1)
    params = fns.init(jax.random.PRNGKey(0))
    prefill = jax.jit(make_prefill_step(fns))
    serve = jax.jit(make_serve_step(fns))

    b, s = args.requests, args.prompt_len
    k_tok, k_vlm, k_enc = jax.random.split(jax.random.PRNGKey(1), 3)
    batch = {"tokens": jax.random.randint(k_tok, (b, s), 0, cfg.vocab_size,
                                          jnp.int32)}
    if cfg.family == "vlm":
        batch["prefix_embeds"] = 0.02 * jax.random.normal(
            k_vlm, (b, cfg.n_prefix_embeds, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        batch["frames"] = 0.02 * jax.random.normal(
            k_enc, (b, enc_len_for(s), cfg.d_model), jnp.bfloat16)

    # warmup: compile prefill + decode outside the timed region so
    # t_prefill / t_decode measure steady-state serving, not XLA compiles
    w_cache, w_tok, _ = prefill(params, batch)
    w_tok, w_cache = serve(params, w_cache, w_tok, jnp.int32(s))
    jax.block_until_ready(w_tok)
    del w_cache, w_tok

    t0 = time.perf_counter()
    cache, tok, _ = prefill(params, batch)
    jax.block_until_ready(tok)
    t_prefill = time.perf_counter() - t0

    # keep device arrays in flight: no per-token host sync (np.asarray
    # inside the loop would block dispatch pipelining every step)
    toks = [tok]
    t0 = time.perf_counter()
    for i in range(args.gen_len - 1):
        tok, cache = serve(params, cache, tok, jnp.int32(s + i))
        toks.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen = np.stack([np.asarray(t) for t in toks], axis=1)
    print(f"arch={cfg.name} requests={b} prompt={s} gen={args.gen_len}")
    print(f"prefill: {t_prefill*1e3:.1f} ms  decode: "
          f"{t_decode/max(args.gen_len-1,1)*1e3:.2f} ms/token/batch")
    print("sample token ids:", gen[0][:12].tolist())
    return 0


def _train_mrf(args, cfg, *, qat_mode: bool):
    """One training recipe for both serving backends — topology comes from
    the arch config (``cfg.mrf_hidden``), so mrf-original serves its own
    (deeper) net, not the adapted one."""
    from repro.core.train_loop import TrainConfig, train

    steps = (args.train_steps if args.train_steps is not None
             else (60 if args.smoke else 600))
    tcfg = TrainConfig(n_frames=cfg.mrf_n_frames, hidden=cfg.mrf_hidden,
                       steps=steps, qat=qat_mode, lr=1e-3, batch_size=256,
                       log_every=max(steps // 3, 1))
    return train(tcfg, verbose=not args.smoke)


def _obtain_int8_artifact(args, cfg):
    """Load ``--artifact`` or QAT-train + export one; always serve the
    saved-then-reloaded form so the smoke exercises the deployment unit."""
    import tempfile

    from repro.core import qat

    if args.artifact:
        return qat.load_int8_artifact(args.artifact)
    params, qstate, _ = _train_mrf(args, cfg, qat_mode=True)
    ints = qat.export_int8(params, qstate)
    # round-trip through disk so the smoke serves the deployment unit, but
    # don't leak a tempdir per run; pass --artifact to serve a kept file
    with tempfile.TemporaryDirectory(prefix="mrf_artifact_") as tmp:
        path = qat.save_int8_artifact(f"{tmp}/{cfg.name}_int8", ints)
        loaded = qat.load_int8_artifact(path)
        print(f"int8 artifact round-tripped via {path.name}")
    return loaded


def _chaos_serve(args, engine, net_kw, requests) -> int:
    """Overload/fault accounting path: enqueue everything, drain through
    the injected schedule, then audit the lifecycle ledger.

    Enqueue-all-then-drain (not enqueue/poll interleaved) on purpose: the
    pending backlog builds before any wave retires, so admission-policy
    shedding is deterministic — the same requests shed every run, which is
    what a CI gate needs.
    """
    import collections

    from repro.serve.queue import RequestState
    from repro.serve.recon import ReconEngine

    tickets = [engine.enqueue(r) for r in requests]
    engine.drain()
    stats, health = engine.last_wave, engine.health()
    states = collections.Counter(t.state for t in tickets)
    print(f"chaos drain: done={states['done']} failed={states['failed']} "
          f"shed={states['shed']} waves={stats['n_waves']} "
          f"retries={stats['n_retries']} slow={health['n_slow_waves']} "
          f"degraded={health['degraded']}")
    for t in tickets:
        if t.state == RequestState.SHED:
            print(f"  shed   {t.request.request_id}: {t.shed_reason}")
        elif t.state == RequestState.FAILED:
            print(f"  failed {t.request.request_id}: {t.error}")
    bad = [t for t in tickets if t.state not in RequestState.TERMINAL]
    if bad:
        print(f"FAIL: {len(bad)} ticket(s) stranded non-terminal: "
              f"{[t.state for t in bad]}")
        return 1
    done = [t for t in tickets if t.state == RequestState.DONE]
    if not done:
        print("FAIL: chaos schedule starved the drain — nothing served")
        return 1
    # every served map must be bit-identical to healthy (fault-free)
    # serving; the reference runs whatever impl the engine ended on (the
    # degraded lax impl is bit-exact vs fused by the PR 7 parity proof)
    ref_kw = dict(net_kw)
    if ref_kw.get("backend") == "int8":
        ref_kw["int8_impl"] = engine.int8_impl
    ref = ReconEngine(**ref_kw)
    for t in done:
        want, = ref.reconstruct([t.request])
        if not (np.array_equal(t.result.t1_ms, want.t1_ms)
                and np.array_equal(t.result.t2_ms, want.t2_ms)):
            print(f"FAIL: served maps diverge from healthy serving "
                  f"({t.request.request_id})")
            return 1
    print(f"served maps == healthy serving: bit-exact ({len(done)} requests)")
    if args.expect_shed and states["shed"] == 0:
        print("FAIL: --expect-shed but the admission policy shed nothing")
        return 1
    if args.expect_degraded and not health["degraded"]:
        print("FAIL: --expect-degraded but the circuit breaker never "
              "tripped")
        return 1
    print("chaos smoke: clean drain, every ticket terminal")
    return 0


def run_mrf_serve(args, cfg) -> int:
    """The MRF reconstruction family through the batched serving engine."""
    from repro.core import qat
    from repro.data.epg import default_sequence
    from repro.data.phantom import acquire_slice, make_phantom, tissue_errors
    from repro.serve.recon import (ReconEngine, ReconRequest,
                                   latency_percentiles)

    backend = args.backend
    if backend not in ("float", "int8"):
        raise SystemExit(f"--backend {backend} is not an MRF serving backend "
                         "(float | int8)")
    if args.artifact and backend != "int8":
        raise SystemExit("--artifact is an int8 deployment unit; it requires "
                         "--backend int8 (float would silently retrain)")
    if args.requests < 1:
        raise SystemExit("--requests must be >= 1 for the mrf family")

    ints = params = None
    if backend == "int8":
        ints = _obtain_int8_artifact(args, cfg)
        impl = None if args.int8_impl == "auto" else args.int8_impl
        net_kw = dict(backend="int8", int_layers=ints, int8_impl=impl)
    else:
        if args.int8_impl != "auto":
            raise SystemExit("--int8-impl selects the full-integer "
                             "implementation; it requires --backend int8")
        params, _, _ = _train_mrf(args, cfg, qat_mode=False)
        net_kw = dict(backend="float", params=params)

    injector = admission = None
    if args.fault_schedule:
        import json

        from repro.serve.faults import FaultInjector
        injector = FaultInjector(json.loads(args.fault_schedule))
    if args.max_pending_voxels is not None or \
            args.shed_deadline_ms is not None:
        from repro.serve.admission import AdmissionPolicy
        admission = AdmissionPolicy(max_pending_voxels=args.max_pending_voxels,
                                    deadline_ms=args.shed_deadline_ms)
    engine = ReconEngine(mode=args.serve_mode,
                         max_wave_voxels=args.max_wave_voxels,
                         max_wait_ms=args.max_wait_ms,
                         admission=admission, injector=injector,
                         adaptive=args.adaptive,
                         wave_timeout_s=(args.wave_timeout_ms * 1e-3
                                         if args.wave_timeout_ms is not None
                                         else None), **net_kw)
    if backend == "int8":
        print(f"int8 impl: {engine.int8_impl} "
              f"(requested {args.int8_impl})")

    # request pool: one phantom slice per request, distinct noise draws
    seq = default_sequence(cfg.mrf_n_frames)
    n = args.phantom_n
    t1_map, t2_map, mask = make_phantom(n)
    requests = []
    for i in range(args.requests):
        feats, msk = acquire_slice(seq, t1_map, t2_map, mask,
                                   key=jax.random.PRNGKey(i))
        requests.append(ReconRequest(features=feats, mask=msk,
                                     request_id=f"slice-{i}"))

    if injector is not None or admission is not None:
        # no warmup wave: it would consume fault-schedule wave indices and
        # pre-feed the admission service rate
        return _chaos_serve(args, engine, net_kw, requests)

    engine.reconstruct(requests)  # warmup wave (compiles buckets)
    if args.serve_mode == "pipelined":
        # streaming admission: enqueue as slices "arrive", poll dispatches
        # due waves mid-stream, drain flushes the rest double-buffered
        tickets = []
        for r in requests:
            tickets.append(engine.enqueue(r))
            engine.poll()
        engine.drain()
        bad = [t for t in tickets if t.result is None]
        if bad:
            for t in bad:
                print(f"FAIL: request {t.request.request_id!r} "
                      f"{t.state}: {t.error}")
            return 1
        results = [t.result for t in tickets]
    else:
        results = engine.reconstruct(requests)
    health = engine.health()
    if health["degraded"] or health["n_kernel_failures"]:
        # healthy serving must not lean on the circuit breaker: a kernel
        # that fails here would otherwise serve from the lax fallback
        print(f"FAIL: engine served degraded ({health['degraded_reason']}; "
              f"{health['n_kernel_failures']} kernel failure(s))")
        return 1
    wave = engine.last_wave
    pct = latency_percentiles(results)
    print(f"arch={cfg.name} backend={backend} mode={args.serve_mode} "
          f"requests={len(requests)} voxels={wave['total_voxels']} "
          f"waves={wave['n_waves']}")
    print(f"throughput: {wave['voxels_per_s']:.0f} voxels/s   latency "
          f"p50 {pct['p50_ms']:.1f} ms  p99 {pct['p99_ms']:.1f} ms")

    if args.serve_mode == "pipelined":
        # pipelining must be a pure scheduling change: same maps, bit-for-bit
        sync_results = ReconEngine(**net_kw).reconstruct(requests)
        for got, want in zip(results, sync_results):
            if not (np.array_equal(got.t1_ms, want.t1_ms)
                    and np.array_equal(got.t2_ms, want.t2_ms)):
                print(f"FAIL: pipelined maps diverge from sync serving "
                      f"({got.request_id})")
                return 1
        print("pipelined == sync serving: bit-exact")
    for name, e in tissue_errors(results[0].t1_ms, results[0].t2_ms,
                                 t1_map, mask).items():
        print(f"  {name:6s}: T1 err {e['T1_err_%']:5.1f}%   "
              f"T2 err {e['T2_err_%']:5.1f}%")

    if backend == "int8":
        # the acceptance check: engine int8 == software integer oracle,
        # bit-for-bit (the paper's FPGA-vs-Python criterion, served)
        from repro.data.pipeline import denormalize_targets
        oracle = qat.int_forward(ints, requests[0].features)
        want_ms = np.asarray(denormalize_targets(oracle))
        vox = np.asarray(mask, bool)
        if not (np.array_equal(results[0].t1_ms[vox], want_ms[:, 0])
                and np.array_equal(results[0].t2_ms[vox], want_ms[:, 1])):
            print("FAIL: int8 engine diverges from qat.int_forward oracle")
            return 1
        print("int8 engine == qat.int_forward oracle: bit-exact")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    # token-family knobs
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    # mrf-family knobs
    ap.add_argument("--backend", default="float",
                    help="mrf-* archs: float | int8 (full-integer Pallas)")
    ap.add_argument("--int8-impl", default="auto",
                    choices=["auto", "fused", "lax", "layered"],
                    help="mrf int8: full-integer implementation — fused = "
                         "whole-network Pallas kernel (TPU deployment "
                         "path), lax = vectorized pure-lax fallback (the "
                         "fast path off-TPU), layered = per-layer kernel "
                         "chain (measured baseline); auto picks per rig. "
                         "All bit-exact vs the qat.int_forward oracle "
                         "(checked below)")
    ap.add_argument("--serve-mode", default="sync",
                    choices=["sync", "pipelined"],
                    help="mrf: sync = per-tile retirement baseline; "
                         "pipelined = double-buffered waves, one host sync "
                         "per wave (bit-identical maps, asserted)")
    ap.add_argument("--max-wave-voxels", type=int, default=None,
                    help="mrf: close a wave at this many voxels "
                         "(default: one wave per drain)")
    ap.add_argument("--max-wait-ms", type=float, default=None,
                    help="mrf: admission deadline from enqueue before a "
                         "wave is due (default: no deadline trigger)")
    ap.add_argument("--fault-schedule", default=None,
                    help="mrf chaos: JSON list of serve.faults FaultSpec "
                         'dicts, e.g. \'[{"kind": "kernel_fail", '
                         '"wave": 0}]\' — switches to the chaos '
                         "accounting path")
    ap.add_argument("--max-pending-voxels", type=int, default=None,
                    help="mrf chaos: admission budget — shed arrivals that "
                         "would push the pending backlog past this")
    ap.add_argument("--shed-deadline-ms", type=float, default=None,
                    help="mrf chaos: shed arrivals whose estimated queue "
                         "wait exceeds this deadline")
    ap.add_argument("--adaptive", action="store_true",
                    help="mrf: auto-tune inflight depth + wave cap from "
                         "observed staging/compute (pipelined mode only)")
    ap.add_argument("--wave-timeout-ms", type=float, default=None,
                    help="mrf: flag waves whose completion wait exceeds "
                         "this as stalls (health accounting)")
    ap.add_argument("--expect-shed", action="store_true",
                    help="mrf chaos: fail unless load shedding engaged")
    ap.add_argument("--expect-degraded", action="store_true",
                    help="mrf chaos: fail unless the int8 circuit breaker "
                         "tripped to the lax impl")
    ap.add_argument("--artifact", default=None,
                    help="mrf int8: serve this .npz artifact instead of "
                         "training one")
    ap.add_argument("--train-steps", type=int, default=None,
                    help="mrf: steps for the in-process training "
                         "(default 60 smoke / 600 full)")
    ap.add_argument("--phantom-n", type=int, default=32,
                    help="mrf: phantom slice side length")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "mrf":
        return run_mrf_serve(args, cfg)
    return run_token_serve(args, cfg)


if __name__ == "__main__":
    raise SystemExit(main())

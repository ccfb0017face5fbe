"""Chip smoke: the MRF main path, once, on one TPU, at the full ``mrf-fpga``
width (32 frames, hidden 64-64-32-16-16-16).

    python3 chip_smoke.py        # from the root of a checkout

Everything runs in this one process, which holds the chip:

* training through ``repro.train.engine`` under ``ft.runner`` — the path
  ``launch.train.run_mrf`` takes — chunked, with the fused-pallas backend
  (in-kernel SGD and in-kernel Adam) and the float backend, each from a
  fresh checkpoint directory; losses must be finite and falling;
* one fused step (SGD and Adam) against the float backend's step from the
  same init and batch, at "highest" matmul precision, to the tolerances of
  tests/test_train_engine.py;
* serving 256x256 phantom slices through ``ReconEngine`` in pipelined
  mode, int8 on the fused kernel and float: every ticket DONE, the engine
  healthy, and the int8 maps bit-equal to the ``qat.int_forward`` oracle
  run on the same chip.

Any failed phase exits non-zero.  Without a TPU it exits non-zero before
any phase.  Rates printed on the way are from a smoke run, not
measurements.  The last line of stdout is the JSON verdict.
"""

from __future__ import annotations

import json
import pathlib
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "mrf-fpga"
BATCH = 256          # per-step batch: two 128-row tiles on the fused kernel
CHUNK_STEPS = 16     # steps per dispatch (one kernel launch on fused-pallas)
STEPS = 64           # four chunks
TRAIN_RUNS = (       # (backend, optimizer, lr)
    ("fused-pallas", "sgd", 1e-2),
    ("fused-pallas", "adam", 1e-3),
    ("float", "adam", 1e-3),
)
PHANTOM_N = 256      # one clinical slice
N_SLICES = 4


class PhaseFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def train_phase(fns, backend, optimizer, lr):
    """Train through the engine exactly as launch.train.run_mrf does."""
    from repro.core.train_loop import evaluate
    from repro.data.pipeline import host_sharded_key
    from repro.ft.runner import RunnerConfig
    from repro.train import engine

    losses, dts = [], []

    def on_metrics(step, metrics, dt):
        losses.append(float(metrics["loss"]))
        dts.append(dt)

    ecfg = engine.EngineConfig(backend=backend, lr=lr, optimizer=optimizer,
                               chunk_steps=CHUNK_STEPS)
    stream = engine.default_stream(fns.cfg, BATCH)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        rcfg = RunnerConfig(total_steps=STEPS, ckpt_dir=ckpt,
                            ckpt_every=STEPS)
        state, step, info = engine.train(
            fns, ecfg, rcfg, stream=stream, data_key=host_sharded_key(seed=1),
            batch_size=BATCH, on_metrics=on_metrics)
    name = f"{backend}/{optimizer}"
    check(step == STEPS and len(losses) == STEPS,
          f"{name}: ran {step} steps, {len(losses)} losses")
    check(all(np.isfinite(losses)), f"{name}: non-finite loss")
    first = float(np.mean(losses[:CHUNK_STEPS]))
    last = float(np.mean(losses[-CHUNK_STEPS:]))
    check(last < first, f"{name}: loss not falling ({first:.6f} -> "
                        f"{last:.6f})")
    m = evaluate(state.params, stream.seq, n=1000)
    print(f"train {name}: loss {first:.6f} -> {last:.6f} over {STEPS} steps; "
          f"first chunk {sum(dts[:CHUNK_STEPS]):.2f} s (compile included); "
          f"{info['samples_per_s']:.0f} samples/s (smoke run, not a "
          f"measurement); T1 MAPE {m['T1']['MAPE_%']:.2f}%  "
          f"T2 MAPE {m['T2']['MAPE_%']:.2f}%", flush=True)
    return state.params


def parity_phase(fns, optimizer):
    """One fused step against the float backend's step from the same init
    and batch (tolerances of tests/test_train_engine.py)."""
    import jax

    from repro.data.pipeline import sample_batch
    from repro.train import engine

    stream = engine.default_stream(fns.cfg, 128)  # one 128-row tile
    x, y = sample_batch(stream, jax.random.PRNGKey(7))
    batch = {"x": x, "y": y}
    lr = 2e-2 if optimizer == "sgd" else 1e-3
    key = jax.random.PRNGKey(0)
    with jax.default_matmul_precision("highest"):
        fused_fn, fused_init = engine.build(fns, engine.EngineConfig(
            backend="fused-pallas", lr=lr, optimizer=optimizer,
            tile_batch=128, donate=False))
        float_fn, float_init = engine.build(fns, engine.EngineConfig(
            backend="float", lr=lr, optimizer=optimizer, donate=False))
        state_k, _ = fused_fn(fused_init(key), batch)
        state_r, _ = float_fn(float_init(key), batch)
    tols = [(state_k.params, state_r.params, 1e-5)]
    if optimizer == "adam":
        tols += [(state_k.opt_state.mu, state_r.opt_state.mu, 1e-5),
                 (state_k.opt_state.nu, state_r.opt_state.nu, 1e-7)]
        check(int(state_k.opt_state.step) == int(state_r.opt_state.step) == 1,
              "adam step counters")
    worst = 0.0
    for got, want, atol in tols:
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            err = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            worst = max(worst, err)
            check(err <= atol, f"fused {optimizer} step differs from the "
                               f"float step by {err:.3e} > {atol:.0e}")
    print(f"parity fused/{optimizer} vs float step: max |diff| {worst:.3e}",
          flush=True)


def serve_phase(backend, net_kw, requests):
    """Serve the slices through the pipelined engine; return the tickets."""
    from repro.serve.queue import RequestState
    from repro.serve.recon import ReconEngine

    engine = ReconEngine(mode="pipelined", **net_kw)
    if backend == "int8":
        check(engine.int8_impl == "fused",
              f"int8 impl resolved to {engine.int8_impl}, not fused")
    t0 = time.perf_counter()
    engine.reconstruct(requests[:1])  # warmup wave: compiles the buckets
    warm_s = time.perf_counter() - t0
    tickets = []
    for r in requests:
        tickets.append(engine.enqueue(r))
        engine.poll()
    engine.drain()
    states = [t.state for t in tickets]
    check(all(s == RequestState.DONE for s in states),
          f"{backend}: tickets ended {states}")
    health = engine.health()
    check(not health["degraded"] and health["n_kernel_failures"] == 0
          and health["n_retries_total"] == 0,
          f"{backend}: unhealthy engine {health}")
    for t in tickets:
        res = t.result
        check(res.t1_ms.shape == (PHANTOM_N, PHANTOM_N)
              and np.all(np.isfinite(res.t1_ms))
              and np.all(np.isfinite(res.t2_ms)), f"{backend}: bad maps")
    wave = engine.last_wave
    print(f"serve {backend} (impl {engine.int8_impl or 'float'}): "
          f"{len(tickets)} slices, {wave['total_voxels']} voxels, "
          f"{wave['n_waves']} waves; warmup {warm_s:.2f} s (compile "
          f"included); {wave['voxels_per_s']:.0f} voxels/s (smoke run, not "
          f"a measurement)", flush=True)
    return tickets


def int8_oracle_check(ints, tickets):
    """Every served int8 map equals qat.int_forward run on this device."""
    from repro.core import qat
    from repro.data.pipeline import denormalize_targets

    for t in tickets:
        want = np.asarray(denormalize_targets(
            qat.int_forward(ints, t.request.features)))
        vox = np.asarray(t.request.mask, bool)
        got = np.stack([t.result.t1_ms[vox], t.result.t2_ms[vox]], axis=1)
        n_bad = int(np.sum(np.any(got != want, axis=1)))
        check(n_bad == 0, f"int8 fused maps differ from the qat.int_forward "
                          f"oracle at {n_bad}/{len(got)} voxels of "
                          f"{t.request.request_id} (max |diff| "
                          f"{float(np.max(np.abs(got - want))):.3e} ms)")
    print(f"int8 fused == qat.int_forward oracle: bit-exact "
          f"({len(tickets)} slices)", flush=True)


def run_phases():
    """Every phase, in order; raises on the first that fails."""
    import jax

    from repro.configs import get_config
    from repro.core import qat
    from repro.data.epg import default_sequence
    from repro.data.phantom import acquire_slice, make_phantom, tissue_errors
    from repro.data.pipeline import sample_batch
    from repro.models import registry
    from repro.serve.recon import ReconRequest
    from repro.train import engine

    cfg = get_config(ARCH)
    fns = registry.build(cfg)
    trained = {}
    for backend, optimizer, lr in TRAIN_RUNS:
        trained[backend, optimizer] = train_phase(fns, backend, optimizer, lr)
    for optimizer in ("sgd", "adam"):
        parity_phase(fns, optimizer)

    # the float-trained net, calibrated and exported as the int8 artifact
    params = trained["float", "adam"]
    x, _ = sample_batch(engine.default_stream(cfg, BATCH),
                        jax.random.PRNGKey(3))
    qstate = qat.init_qat_state(len(params))
    for _ in range(3):
        _, qstate = qat.forward_qat(params, qstate, x)
    ints = qat.export_int8(params, qstate)

    seq = default_sequence(cfg.mrf_n_frames)
    t1_map, t2_map, mask = make_phantom(PHANTOM_N)
    requests = []
    for i in range(N_SLICES):
        feats, msk = acquire_slice(seq, t1_map, t2_map, mask,
                                   key=jax.random.PRNGKey(i))
        requests.append(ReconRequest(features=feats, mask=msk,
                                     request_id=f"slice-{i}"))
    int8_tickets = serve_phase("int8", dict(backend="int8", int_layers=ints),
                               requests)
    int8_oracle_check(ints, int8_tickets)
    float_tickets = serve_phase("float", dict(backend="float", params=params),
                                requests)
    for name, tickets in (("int8", int8_tickets), ("float", float_tickets)):
        res = tickets[0].result
        errs = tissue_errors(res.t1_ms, res.t2_ms, t1_map, mask)
        print(f"  {name} slice-0 tissue error: " + "  ".join(
            f"{k} T1 {e['T1_err_%']:.1f}% T2 {e['T2_err_%']:.1f}%"
            for k, e in errs.items()), flush=True)


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}", flush=True)
    t0 = time.perf_counter()
    try:
        run_phases()
    except PhaseFailed as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

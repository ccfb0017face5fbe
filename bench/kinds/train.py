"""Training cells: the program's fused training engine under ``ft.runner``.

Set-up builds one chunk dispatcher (``engine.build_chunked``, the object
``engine.train`` builds for ``launch.train.run_mrf``), initialises its
state from the seed in one jitted call, and drives that state through the
first checked steps with the dispatcher itself: step 1 alone, whose
parameters give the first gradient, then one launch of the window's own
chunk length (``chunk_steps`` steps with the weights held in VMEM across
them), whose per-step losses and end state the reference follows.  It then
compiles every other chunk length the window will dispatch, on a state of
its own, and times ``ft.runner.run`` on that state over whole checkpoint
periods to size the window.  The window resumes the checked state from a
fresh checkpoint directory and runs ``ft.runner.run`` to a whole number of
checkpoint periods, saving every ``ckpt_every`` steps as the launcher does;
its end state is kept to check that every moving leaf moved and stayed
finite.  Set-up and window both run under the configuration's matmul
precision, which the harness sets: ``launch.train`` sets none (PERF.md).

``engine.train`` itself is not called: it builds a new jitted dispatcher on
every call, so set-up could not hand the window the object it compiled.
"""

from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, reference, seeds, sim, work

# seconds of runner time that set-up spends to size the window
RATE_SECONDS = 0.5


def chunk_lengths(start: int, total: int, chunk: int, every: int) -> list:
    """The chunk lengths ``ft.runner``'s chunked loop dispatches from
    ``start`` to ``total``: full chunks, clipped at checkpoint periods."""
    out, step = [], start
    while step < total:
        n = min(chunk, total - step, (step // every + 1) * every - step)
        out.append(n)
        step += n
    return out


def _host(tree):
    return jax.tree.map(lambda a: np.array(a), jax.device_get(tree))


class TrainCell:
    def __init__(self, cell, seed: int, precision: str | None = "config"):
        """``precision``: the matmul precision the program runs under, the
        configuration's by default; ``None`` leaves JAX's default, the
        program's own lower-precision path, for the control."""
        self.cell, self.seed = cell, seed
        self.tr = cell.traffic
        self.sizes = work.layer_sizes(cell.config)
        self.precision = (cell.config["precision"]["train"]
                          if precision == "config" else precision)
        # the checked steps: step 1 alone, then one launch of the window's
        # chunk length
        self.start = 1 + int(self.tr["chunk_steps"])

    # -- set-up --------------------------------------------------------------

    def setup(self, seconds: float) -> None:
        # the configuration's matmul precision is JAX's option, which the
        # fused kernel's dots honour; without it Mosaic multiplies float32
        # at its own default, below what the configuration states
        with jax.default_matmul_precision(self.precision):
            self._setup(seconds)

    def _setup(self, seconds: float) -> None:
        from repro.configs import get_config
        from repro.data.pipeline import host_sharded_key
        from repro.kernels.fused_train import ops as fused_ops
        from repro.models import registry
        from repro.train import engine

        tr, cfg = self.tr, self.cell.config
        mcfg = dataclasses.replace(get_config(cfg["program_arch"]),
                                   mrf_n_frames=int(cfg["n_frames"]),
                                   mrf_hidden=tuple(cfg["hidden"]))
        fns = registry.build(mcfg)
        ecfg = engine.EngineConfig(backend=tr["backend"], lr=tr["lr"],
                                   optimizer=tr["optimizer"],
                                   tile_batch=tr["tile_batch"],
                                   chunk_steps=tr["chunk_steps"])
        stream = engine.default_stream(mcfg, tr["batch"])
        # the data key is a constant of the compiled chunk; varying it per
        # seed would compile every run anew, so seeds vary the weights
        data_key = host_sharded_key(seed=tr["data_seed"])
        self.chunk_fn, init_state = engine.build_chunked(fns, ecfg, stream,
                                                         data_key)
        fused_ops.effective_tile(tr["batch"], tr["tile_batch"])
        init = jax.jit(init_state)

        state = init(seeds.key(self.seed, "init"))
        first = {"p0": _host(state.params), "losses": []}
        for start, n in ((0, 1), (1, self.start - 1)):
            state, metrics = self.chunk_fn(state, start, n)
            first["losses"] += [float(v) for v in
                                jax.device_get(metrics["loss"])]
            if start == 0:
                first["p1"] = _host(state.params)
                if tr["optimizer"] == "adam":
                    first["mu1"] = _host(state.opt_state.mu)
        first["pn"] = _host(state.params)
        self.first, self.state = first, state

        # on a state of its own: compile every chunk length the runner
        # dispatches, then time the runner itself, checkpoints included,
        # to size the window to ``seconds``
        chunk, every = tr["chunk_steps"], tr["ckpt_every"]
        warm = init(seeds.key(self.seed, "warm"))
        for n in sorted(set(chunk_lengths(self.start, 2 * every, chunk,
                                          every))):
            warm, m = self.chunk_fn(warm, every - n, n)
        jax.block_until_ready(m)
        periods, rate = 1, 0.0
        step = every
        while True:
            t0 = time.perf_counter()
            warm, step = self._run(warm, step, step + periods * every)
            dt = time.perf_counter() - t0
            rate = periods * every / dt
            if dt >= RATE_SECONDS:
                break
            periods = max(2 * periods,
                          int(np.ceil(RATE_SECONDS * rate / every)))
        del warm
        self.total = max(2, int(round((self.start + rate * seconds)
                                      / every))) * every

    def _run(self, state, start: int, total: int):
        """``ft.runner.run`` from ``state`` at step ``start`` to ``total``,
        in a fresh checkpoint directory, as the launcher runs it."""
        from repro.ft.checkpoint import save_state
        from repro.ft.runner import RunnerConfig, run

        tr = self.tr
        ckpt = tempfile.mkdtemp(prefix="bench_ckpt_")
        try:
            save_state(state, ckpt, start, async_io=False)
            rcfg = RunnerConfig(total_steps=total, ckpt_dir=ckpt,
                                ckpt_every=tr["ckpt_every"])
            state, step = run(None, state, None, rcfg, chunk_fn=self.chunk_fn,
                              chunk_steps=tr["chunk_steps"])
            jax.block_until_ready(state)
        finally:
            shutil.rmtree(ckpt, ignore_errors=True)
        return state, step

    # -- window --------------------------------------------------------------

    def window(self) -> dict:
        tr = self.tr
        with jax.default_matmul_precision(self.precision):
            with jax.profiler.TraceAnnotation("train"):
                t0 = time.perf_counter()
                state, step = self._run(self.state, self.start, self.total)
                t1 = time.perf_counter()
        self.end = _host(state.params)
        del state
        self.state = None
        steps = step - self.start
        self.counters = {
            "window_s": t1 - t0, "steps": steps,
            "samples": steps * tr["batch"],
            "attempted": self.total - self.start,
            "failed": self.total - step}
        return self.counters

    # -- work at the configuration's real widths (bench/work.py) -------------

    def ops_per_sample(self) -> int:
        """Forward and backward operations of one training sample."""
        return work.train_ops_per_row(self.sizes)

    def kernel_work(self, kernel: str, rows: int, launches: int):
        """(operations, HBM bytes) of ``launches`` launches of ``kernel``
        over ``rows`` rows in all, or None for a kernel this kind does not
        count."""
        if kernel != "fused_train":
            return None
        return (rows * work.train_ops_per_row(self.sizes),
                work.train_kernel_bytes(self.sizes, rows, launches,
                                        self.tr["optimizer"]))

    # -- correctness -----------------------------------------------------------

    def readings(self, precision: str = "highest",
                 keep_rows: int | None = None) -> dict:
        """The reference's checked steps, as the dict
        ``compare.train_numbers`` takes.  ``precision`` or ``keep_rows``
        other than the reference's make it the control or a planted fault."""
        tr, cfg = self.tr, self.cell.config
        params = reference.init_params(seeds.key(self.seed, "init"),
                                       self.sizes)
        opt = (reference.adam_init(params) if tr["optimizer"] == "adam"
               else None)
        seq = sim.default_sequence(int(cfg["n_frames"]))
        data_key = jax.random.fold_in(jax.random.PRNGKey(tr["data_seed"]), 0)
        out = {"p0": _host(params), "losses": []}
        for step in range(self.start):
            x, y = sim.sample_batch(
                jax.random.fold_in(data_key, step), seq=seq,
                batch=tr["batch"], t1_range=tuple(cfg["t1_range_ms"]),
                t2_range=tuple(cfg["t2_range_ms"]),
                snr_range=tuple(tr["snr_range"]))
            params, opt, losses = reference.train_step(
                params, opt, x, y, optimizer=tr["optimizer"], lr=tr["lr"],
                tile=tr["tile_batch"], precision=precision,
                keep_rows=keep_rows)
            out["losses"].append(float(jnp.mean(losses)))
            if step == 0:
                out["p1"] = _host(params)
                if tr["optimizer"] == "adam":
                    out["mu1"] = _host(opt[1])
        out["pn"] = _host(params)
        return out

    def check(self) -> dict:
        """The checked steps against the reference and, once a window has
        run, its end state against its start (``compare.end_numbers``)."""
        ref = self.readings(self.cell.config["precision"]["train"])
        out = compare.train_numbers(self.first, ref, self.tr["optimizer"],
                                    self.tr["lr"])
        if getattr(self, "end", None) is not None:
            out.update(compare.end_numbers(
                self.first["pn"], self.end,
                compare.moving_leaves(compare.grads(ref, self.tr["optimizer"],
                                                    self.tr["lr"]))))
        return out

    def control(self) -> dict:
        """{name: numbers} of the control and the planted fault, each put in
        the program's place against the reference: the reference at one
        bfloat16 pass, and the reference with half of each tile's rows left
        out.  ``look_high``, the reference at three bfloat16 passes, is a
        look and no control: its readings fall among sound runs'."""
        ref = self.readings(self.cell.config["precision"]["train"])
        opt, lr = self.tr["optimizer"], self.tr["lr"]
        return {
            "look_high": compare.train_numbers(
                self.readings("high"), ref, opt, lr),
            "control_bfloat16": compare.train_numbers(
                self.readings("bfloat16"), ref, opt, lr),
            "half_batch": compare.train_numbers(
                self.readings(keep_rows=self.tr["tile_batch"] // 2), ref,
                opt, lr)}

    def program_control(self, precision: str | None = None) -> dict:
        """The numbers of the program's own path below the configuration's
        precision (set-up's checked steps at JAX's matmul ``precision``,
        its default if ``None``) against the reference: the control where
        the program has such a path."""
        c = TrainCell(self.cell, self.seed, precision=precision)
        c.setup(0.0)
        return c.check()


CELL = TrainCell

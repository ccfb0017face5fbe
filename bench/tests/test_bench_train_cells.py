"""CPU rehearsal of the training cells: one short window at a tiny size
through the harness, then the same run with a broken step underneath,
which ``correct`` has to catch, and the control, which has to fail."""

import dataclasses
import time

import jax.numpy as jnp
import pytest

from bench import harness, spec
from bench.kinds.train import TrainCell, chunk_lengths
from bench.tests.conftest import small_cell

CELLS = ["fpga-train-sgd"]
# the cell's own rule, and in-kernel Adam through the same harness
OPTIMIZERS = {"sgd": {}, "adam": {"optimizer": "adam", "lr": 1e-3}}
SEED = 2**31 + 11      # larger than a signed 32-bit int


def run(name, seconds=0.3, optimizer="sgd"):
    cell = small_cell(name)
    cell = dataclasses.replace(
        cell, traffic={**cell.traffic, **OPTIMIZERS[optimizer]})
    return harness.run(cell, SEED, seconds, False, time.perf_counter())


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(name, optimizer, cpu_peaks):
    r, lowered, _ = run(name, optimizer=optimizer)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"setup_s", "train_samples_per_s"}
    assert r["metrics"]["train_samples_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(small_cell(name).limits)
    assert lowered == 0


def _patch_multistep(monkeypatch, fault):
    """Break the fused multi-step launch under the engine.  The faults
    named ``*_multi`` break only launches of more than one step, so only
    a check of the window's own chunk length can see them."""
    from repro.kernels.fused_train import ops

    real = ops.fused_train_multistep

    def broken(params, opt_state, x, y, *, n_steps, **kw):
        b = x.shape[0] // n_steps
        if fault.endswith("_multi") and n_steps == 1:
            return real(params, opt_state, x, y, n_steps=n_steps, **kw)
        if fault.startswith("unchanged"):
            _, _, losses = real(params, opt_state, x, y, n_steps=n_steps, **kw)
            return params, opt_state, losses
        if fault == "not_carried_multi":
            # every step from the launch's first weights, the last one kept
            outs = [real(params, opt_state, x[k * b:(k + 1) * b],
                         y[k * b:(k + 1) * b], n_steps=1, **kw)
                    for k in range(n_steps)]
            return (outs[-1][0], outs[-1][1],
                    jnp.concatenate([o[2] for o in outs]))
        if fault == "same_batch_multi":
            # every step on the launch's first batch
            rep = lambda a: jnp.tile(a[:b], (n_steps, 1))
            return real(params, opt_state, rep(x), rep(y), n_steps=n_steps,
                        **kw)
        # half of each step's rows left out, the mean over the rest
        keep = lambda a: a.reshape(n_steps, b, -1)[:, :b // 2].reshape(
            n_steps * (b // 2), -1)
        return real(params, opt_state, keep(x), keep(y), n_steps=n_steps,
                    **kw)

    monkeypatch.setattr(ops, "fused_train_multistep", broken)


@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "unchanged_multi", "not_carried_multi",
                                   "same_batch_multi"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_step_is_not_correct(name, fault, optimizer, cpu_peaks,
                                    monkeypatch):
    _patch_multistep(monkeypatch, fault)
    r, _, _ = run(name, optimizer=optimizer)
    assert not r["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_window_end_state_is_checked(name, cpu_peaks, monkeypatch):
    """A window that leaves the state where set-up handed it over, or
    makes it non-finite, is not correct though the checked steps are."""
    from bench.kinds import train

    r, _, numbers = run(name)
    assert numbers["end_unmoved"] == 0 and numbers["end_nonfinite"] == 0
    real = train.TrainCell._run

    def stalled(self, state, start, total):
        if start != self.start:          # set-up's timing runs
            return real(self, state, start, total)
        return state, total

    monkeypatch.setattr(train.TrainCell, "_run", stalled)
    r, _, numbers = run(name)
    assert not r["correct"] and numbers["end_unmoved"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_and_fault_fail_the_limits(name):
    """At the cell's own sizes: the control and the fault need only the
    reference, which the CPU runs in seconds."""
    cell = spec.load(name)
    out = TrainCell(cell, SEED).control()
    for which, numbers in out.items():
        if which.startswith("look_"):
            continue
        assert any(numbers[k] > lim for k, lim in cell.limits.items()
                   if k in numbers), (which, numbers)


def test_chunk_lengths_follow_the_runner():
    # the window starts after step 1 and one 16-step launch
    assert chunk_lengths(17, 200, 16, 100) == [16] * 5 + [3] + [16] * 6 + [4]
    assert set(chunk_lengths(17, 1000, 16, 100)) == {16, 3, 4}
    assert chunk_lengths(3, 200, 16, 100) == [16] * 6 + [1] + [16] * 6 + [4]


def test_reference_backprop_matches_autodiff():
    import jax

    from bench import reference

    key = jax.random.PRNGKey(0)
    params = reference.init_params(key, (8, 16, 4, 2))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (8, 2))

    def loss(p):
        h = x
        for i, layer in enumerate(p):
            z = jnp.dot(h, layer["w"], precision="highest") + layer["b"]
            h = z if i == len(p) - 1 else jnp.maximum(z, 0.0)
        return jnp.mean((h - y) ** 2)

    want_l, want_g = jax.value_and_grad(loss)(params)
    got_l, got_g = reference.tile_loss_and_grads(params, x, y, "highest")
    assert got_l == pytest.approx(float(want_l), rel=1e-6)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-7)


def test_reference_init_is_the_published_init():
    """The reference draws the program's weights from the same seed
    without taking them from the program."""
    import jax

    from bench import reference
    from repro.core import mrf_net

    key = jax.random.PRNGKey(7)
    sizes = (64, 64, 32, 2)
    for a, b in zip(jax.tree.leaves(reference.init_params(key, sizes)),
                    jax.tree.leaves(mrf_net.init_params(key, sizes))):
        assert jnp.array_equal(a, b)

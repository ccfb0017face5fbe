"""The checkpoint's small-leaf pack (``ft/checkpoint``): a save's small leaves
cross to the host in one fetch and land in one file, restore bit for bit
with their dtypes, leave large leaves per shard, keep every file operation
off the caller's thread, and checkpoints in the older one-file-per-leaf
layout still restore."""

import collections
import json
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.ft import checkpoint
from repro.ft.checkpoint import latest_step, restore_state, save_state

jax.config.update("jax_platform_name", "cpu")


def _mixed_tree():
    key = jax.random.PRNGKey(3)
    return {"w": jax.random.normal(key, (7, 5), jnp.float32),
            "step": jnp.int32(41),
            "bf": jax.random.normal(key, (3, 3), jnp.bfloat16),
            "bytes": jnp.arange(13, dtype=jnp.uint8),
            "half": jnp.linspace(-1, 1, 9, dtype=jnp.float16),
            "empty": jnp.zeros((0,), jnp.float32),
            "tail": jnp.float32(-0.0)}


def _like(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _assert_bit_equal(want, got):
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert b.dtype == a.dtype and b.shape == a.shape
        np.testing.assert_array_equal(np.asarray(b).reshape(-1).view(np.uint8),
                                      np.asarray(a).reshape(-1).view(np.uint8))


def _files(step_dir):
    return sorted(str(p.relative_to(step_dir)) for p in step_dir.rglob("*")
                  if p.is_file())


@pytest.mark.parametrize("async_io", [False, True])
def test_pack_round_trip_is_bit_exact(tmp_path, async_io):
    tree = _mixed_tree()
    save_state(tree, tmp_path, 5, async_io=async_io)()
    _assert_bit_equal(tree, restore_state(_like(tree), tmp_path, 5))
    manifest = json.loads((tmp_path / "step_5" / "manifest.json").read_text())
    offsets = [leaf["offset"] for leaf in manifest["leaves"]]
    assert all(o % 64 == 0 for o in offsets)
    assert offsets == sorted(offsets)
    assert _files(tmp_path / "step_5") == [checkpoint.PACK, "manifest.json"]


def test_large_leaf_keeps_its_shard_file(tmp_path):
    big = jax.random.normal(jax.random.PRNGKey(4), (513, 512), jnp.float32)
    assert big.nbytes > checkpoint._SMALL
    tree = {"big": big, "small": jnp.arange(6.0)}
    save_state(tree, tmp_path, 2, async_io=False)
    step_dir = tmp_path / "step_2"
    manifest = json.loads((step_dir / "manifest.json").read_text())
    big_info = manifest["leaves"][0]
    assert [s["index"] for s in big_info["shards"]] == [[[0, 513], [0, 512]]]
    assert "offset" not in big_info and "offset" in manifest["leaves"][1]
    assert _files(step_dir) == ["leaf_0/shard_0.npy", checkpoint.PACK,
                                "manifest.json"]
    _assert_bit_equal(tree, restore_state(_like(tree), tmp_path, 2))


@pytest.mark.parametrize("n_leaves", [1, 5, 40])
def test_step_file_count_does_not_grow_with_small_leaves(tmp_path, n_leaves):
    tree = [jnp.full((i + 1,), i, jnp.float32) for i in range(n_leaves)]
    save_state(tree, tmp_path, 1, async_io=False)
    assert _files(tmp_path / "step_1") == [checkpoint.PACK, "manifest.json"]
    _assert_bit_equal(tree, restore_state(_like(tree), tmp_path, 1))


def test_per_leaf_layout_still_restores(tmp_path):
    """A checkpoint written one ``.npy`` per small leaf, large leaves per
    shard, with the manifest of that layout."""
    small = np.arange(12, dtype=np.float32).reshape(3, 4)
    scalar = np.asarray(7, np.int32)
    big = np.arange(2 * 6, dtype=np.float32).reshape(2, 6)
    step_dir = tmp_path / "step_9"
    (step_dir / "leaf_2").mkdir(parents=True)
    np.save(step_dir / "leaf_0.npy", small)
    np.save(step_dir / "leaf_1.npy", scalar)
    np.save(step_dir / "leaf_2" / "shard_0.npy", big[:1])
    np.save(step_dir / "leaf_2" / "shard_1.npy", big[1:])
    manifest = {"step": 9, "treedef_repr": "", "n_leaves": 3, "leaves": [
        {"shape": [3, 4], "dtype": "float32", "shards": [],
         "file": "leaf_0.npy"},
        {"shape": [], "dtype": "int32", "shards": [], "file": "leaf_1.npy"},
        {"shape": [2, 6], "dtype": "float32", "shards": [
            {"file": "leaf_2/shard_0.npy", "index": [[0, 1], [0, 6]]},
            {"file": "leaf_2/shard_1.npy", "index": [[1, 2], [0, 6]]}]}]}
    (step_dir / "manifest.json").write_text(json.dumps(manifest))
    (tmp_path / "LATEST").write_text("9")

    want = [jnp.asarray(small), jnp.asarray(scalar), jnp.asarray(big)]
    assert latest_step(tmp_path) == 9
    _assert_bit_equal(want, restore_state(_like(want), tmp_path))


@pytest.mark.parametrize("n_small,n_big", [(7, 0), (2, 1), (0, 1)])
def test_save_counts_one_fetch_for_the_pack(tmp_path, n_small, n_big):
    tree = ([jnp.ones((i + 2,), jnp.int32) for i in range(n_small)]
            + [jnp.zeros((513, 512), jnp.float32) for _ in range(n_big)])
    before = collections.Counter(obs.COUNTS)
    for step in (1, 2):
        save_state(tree, tmp_path, step, async_io=False)
    delta = {k: obs.COUNTS[k] - before[k]
             for k in ("d2h", "ckpt.packed_leaves", "ckpt.saves")}
    assert delta == {"d2h": 2 * ((n_small > 0) + n_big),
                     "ckpt.packed_leaves": 2 * n_small, "ckpt.saves": 2}


def test_async_save_does_no_file_io_on_the_caller(tmp_path):
    """The synchronous part of an async save copies to the host and builds
    the manifest; every file and directory appears only once the flush runs
    on the worker."""
    directory = tmp_path / "ckpt"
    release = threading.Event()
    worker = ThreadPoolExecutor(max_workers=1)
    worker.submit(release.wait)  # the flush queues behind this
    tree = _mixed_tree()
    try:
        wait = save_state(tree, directory, 3, async_io=True,
                          _executor=worker)
        assert not directory.exists()
        assert list(tmp_path.iterdir()) == []
    finally:
        release.set()
    wait(timeout=60)
    worker.shutdown()
    assert latest_step(directory) == 3
    assert not list(directory.glob(".tmp_step_*"))
    _assert_bit_equal(tree, restore_state(_like(tree), directory))

"""Sharded, mesh-shape-agnostic checkpointing (no tensorstore dependency).

Layout:
    <dir>/step_<N>/
        manifest.json            tree structure + per-leaf shape/dtype and
                                 where the leaf's bytes are
        leaves.bin               every small leaf, raw bytes, each at an
                                 offset aligned to 64 (manifest: ``offset``,
                                 ``nbytes``)
        leaf_<i>/shard_<j>.npy   large leaves: one file per distinct
                                 addressable shard (manifest: ``shards``)
    <dir>/LATEST                 atomic pointer (tmp+rename)

A leaf is small when it is at most ``_SMALL`` bytes or has no
``addressable_shards``.  The small leaves of a save cross to the host in one
``jax.device_get`` (every copy started before any is waited on) and land in
one file with one write; a save's file count does not grow with them.
Checkpoints written one ``leaf_<i>.npy`` per small leaf (manifest:
``file``), as older versions did, still restore.

Each shard file records its *global index* (slices into the global array), so
restore can reassemble onto ANY mesh/sharding — the elastic-scaling property:
a checkpoint from a 256-chip run restores onto 512 chips or 8 (DESIGN.md §5).

Async mode: the device->host copy and the manifest happen synchronously; all
file IO (the temporary directory, writes, rename, ``LATEST``, clean-up) on a
background thread so the train loop isn't blocked (the standard async-ckpt
split).  ``CheckpointManager`` keeps the last K checkpoints and handles
resume-from-latest.

Spans (``repro.obs``): ``ckpt.snapshot`` is the synchronous part of a save
(counting ``ckpt.saves``, ``ckpt.bytes``, ``ckpt.packed_leaves`` and a
``d2h`` per host copy: one for the small leaves, one per large shard),
``ckpt.flush`` the file writes, rename and clean-up (on the worker thread
when async), ``ckpt.wait`` a wait on the previous flush and
``ckpt.restore`` a resume.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

_SMALL = 1 << 20  # leaves up to 1 MiB go into the pack as global arrays
PACK = "leaves.bin"
_ALIGN = 64  # byte alignment of each leaf in the pack


def _leaf_paths(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def _index_to_json(idx, shape):
    out = []
    for sl, dim in zip(idx, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        out.append([start, stop])
    return out


def save_state(state, directory, step: int, *, async_io: bool = True,
               on_flushed=None, _executor=ThreadPoolExecutor(max_workers=2)):
    """Save a pytree of (possibly sharded) jax arrays. Returns a wait() fn.
    ``on_flushed()``, if given, runs once the checkpoint has landed, as part
    of the flush."""
    directory = pathlib.Path(directory)
    tmp = directory / f".tmp_step_{step}"
    final = directory / f"step_{step}"
    with obs.span("ckpt.snapshot", step=step):
        manifest, packed, shards = _snapshot(state, step)
    obs.count("ckpt.saves")
    obs.count("ckpt.bytes", sum(h.nbytes for _off, h in packed)
              + sum(h.nbytes for _fn, h in shards))
    obs.count("ckpt.packed_leaves", len(packed))
    obs.count("d2h", (1 if packed else 0) + len(shards))

    def flush():
        with obs.span("ckpt.flush", step=step):
            if tmp.exists():
                shutil.rmtree(tmp)
            tmp.mkdir(parents=True)
            (tmp / PACK).write_bytes(_pack(packed, manifest["pack_nbytes"]))
            for fn, host in shards:
                path = tmp / fn
                path.parent.mkdir(exist_ok=True)
                np.save(path, host)
            (tmp / "manifest.json").write_text(json.dumps(manifest))
            if final.exists():
                shutil.rmtree(final)
            os.rename(tmp, final)
            latest_tmp = directory / ".LATEST.tmp"
            latest_tmp.write_text(str(step))
            os.replace(latest_tmp, directory / "LATEST")
            if on_flushed is not None:
                on_flushed()

    if async_io:
        fut = _executor.submit(flush)
        return fut.result  # wait() function
    flush()
    return lambda: None


def _snapshot(state, step: int):
    """(manifest, [(pack offset, host array)], [(shard file, host array)]):
    the state copied to the host, the small leaves in one fetch, a large
    sharded leaf per distinct shard."""
    leaves, treedef = _leaf_paths(state)
    # tree structure is carried by the restore-side `like` tree (restore_state
    # asserts leaf counts); record the repr for human debugging only.
    manifest = {"step": step, "treedef_repr": str(treedef)[:2000],
                "n_leaves": len(leaves), "pack": PACK, "leaves": []}
    small = [i for i, leaf in enumerate(leaves)
             if not (hasattr(leaf, "addressable_shards")
                     and leaf.nbytes > _SMALL)]
    # one device_get starts every leaf's copy before it waits on any
    fetched = jax.device_get([leaves[i] for i in small])
    hosts = {i: np.asarray(h) for i, h in zip(small, fetched)}
    packed, shards, offset = [], [], 0
    for i, leaf in enumerate(leaves):
        if i in hosts:
            host = hosts[i]
            manifest["leaves"].append(
                {"shape": list(host.shape), "dtype": str(host.dtype),
                 "offset": offset, "nbytes": host.nbytes})
            packed.append((offset, host))
            offset += -(-host.nbytes // _ALIGN) * _ALIGN
            continue
        info = {"shape": list(leaf.shape), "dtype": str(leaf.dtype),
                "shards": []}
        for shard in leaf.addressable_shards:
            idx = _index_to_json(shard.index, leaf.shape)
            # skip duplicate replicas: only save the first owner
            if any(s["index"] == idx for s in info["shards"]):
                continue
            fn = f"leaf_{i}/shard_{len(info['shards'])}.npy"
            info["shards"].append({"file": fn, "index": idx})
            shards.append((fn, np.asarray(shard.data)))
        manifest["leaves"].append(info)
    manifest["pack_nbytes"] = offset
    return manifest, packed, shards


def _pack(packed, nbytes: int) -> np.ndarray:
    """The pack file's bytes: each small leaf's at its offset."""
    buf = np.zeros(nbytes, np.uint8)
    for offset, host in packed:
        raw = host.reshape(-1).view(np.uint8)
        buf[offset:offset + raw.size] = raw
    return buf


def latest_step(directory) -> int | None:
    p = pathlib.Path(directory) / "LATEST"
    if not p.exists():
        return None
    return int(p.read_text().strip())


def restore_state(like, directory, step: int | None = None, *,
                  shardings=None):
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs).  ``shardings``: optional matching pytree of
    NamedSharding to place leaves onto (elastic restore onto a new mesh)."""
    directory = pathlib.Path(directory)
    if step is None:
        step = latest_step(directory)
        assert step is not None, f"no checkpoint under {directory}"
    d = directory / f"step_{step}"
    manifest = json.loads((d / "manifest.json").read_text())
    leaves, treedef = _leaf_paths(like)
    assert len(leaves) == manifest["n_leaves"], "tree structure mismatch"
    shard_leaves = (jax.tree.leaves(shardings) if shardings is not None
                    else [None] * len(leaves))
    pack = (d / manifest["pack"]).read_bytes() if "pack" in manifest else None

    out = []
    for i, (leaf, info) in enumerate(zip(leaves, manifest["leaves"])):
        dtype = jnp.dtype(info["dtype"])
        if "offset" in info:
            host = np.frombuffer(pack, dtype, info["nbytes"] // dtype.itemsize,
                                 info["offset"]).reshape(info["shape"])
        elif "file" in info:  # one .npy per small leaf (older checkpoints)
            host = np.load(d / info["file"])
        else:
            host = np.zeros(info["shape"], dtype=dtype)
            for s in info["shards"]:
                idx = tuple(slice(a, b) for a, b in s["index"])
                host[idx] = np.load(d / s["file"])
        if shard_leaves[i] is not None:
            out.append(jax.device_put(host, shard_leaves[i]))
        else:
            out.append(jax.device_put(host))
    return jax.tree.unflatten(jax.tree.structure(like), out)


class CheckpointManager:
    """Keep-last-K manager with async save and resume."""

    def __init__(self, directory, *, keep: int = 3, every: int = 100):
        self.dir = pathlib.Path(directory)
        self.keep = keep
        self.every = every
        self._pending = None
        self._lock = threading.Lock()

    def maybe_save(self, state, step: int, *, force: bool = False) -> bool:
        """Save if ``step`` is on the period — or unconditionally with
        ``force`` (eviction snapshots land wherever the straggler fired)."""
        if not force and step % self.every:
            return False
        self.wait()
        # GC only after the rename landed, on the flush's thread
        self._pending = save_state(state, self.dir, step, async_io=True,
                                   on_flushed=self._gc)
        return True

    def wait(self):
        with self._lock:
            if self._pending is not None:
                with obs.span("ckpt.wait"):
                    self._pending()
                self._pending = None

    def _gc(self):
        steps = sorted(int(p.name.split("_")[1])
                       for p in self.dir.glob("step_*"))
        for s in steps[:-self.keep]:
            shutil.rmtree(self.dir / f"step_{s}", ignore_errors=True)

    def restore_latest(self, like, shardings=None):
        with obs.span("ckpt.restore"):
            step = latest_step(self.dir)
            if step is None:
                return None, 0
            return (restore_state(like, self.dir, step, shardings=shardings),
                    step)

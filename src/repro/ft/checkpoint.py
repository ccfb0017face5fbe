"""Sharded, mesh-shape-agnostic checkpointing (no tensorstore dependency).

Layout:
    <dir>/step_<N>/
        manifest.json            tree structure + leaf shapes/dtypes
        leaf_<i>/shard_<j>.npy   one file per addressable shard
        leaf_<i>.npy             (small leaves: single global array)
    <dir>/LATEST                 atomic pointer (tmp+rename)

Each shard file records its *global index* (slices into the global array), so
restore can reassemble onto ANY mesh/sharding — the elastic-scaling property:
a checkpoint from a 256-chip run restores onto 512 chips or 8 (DESIGN.md §5).

Async mode: device->host transfer happens synchronously (cheap), file IO on a
background thread so the train loop isn't blocked (the standard async-ckpt
split).  ``CheckpointManager`` keeps the last K checkpoints and handles
resume-from-latest.

Spans (``repro.obs``): ``ckpt.snapshot`` is the synchronous part of a save
(counting ``ckpt.saves``, ``ckpt.bytes`` and a ``d2h`` per host copy),
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
import numpy as np

from repro import obs

_SMALL = 1 << 20  # leaves below 1 MiB are stored as single global arrays


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
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        manifest, work = _snapshot(state, tmp, step)
    obs.count("ckpt.saves")
    obs.count("ckpt.bytes", sum(host.nbytes for _path, host in work))
    obs.count("d2h", len(work))

    def flush():
        with obs.span("ckpt.flush", step=step):
            for path, host in work:
                path.parent.mkdir(parents=True, exist_ok=True)
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


def _snapshot(state, tmp, step: int):
    """(manifest, [(file path, host array)]): the state copied to the host,
    one array per leaf, or per distinct shard of a large sharded leaf."""
    leaves, treedef = _leaf_paths(state)
    # tree structure is carried by the restore-side `like` tree (restore_state
    # asserts leaf counts); record the repr for human debugging only.
    manifest = {"step": step, "treedef_repr": str(treedef)[:2000],
                "n_leaves": len(leaves), "leaves": []}
    work = []
    for i, leaf in enumerate(leaves):
        arr = leaf
        info = {"shape": list(np.shape(arr)),
                "dtype": str(np.asarray(jax.tree.leaves(arr)[0]).dtype)
                if not hasattr(arr, "dtype") else str(arr.dtype),
                "shards": []}
        if hasattr(arr, "addressable_shards") and arr.nbytes > _SMALL:
            for j, shard in enumerate(arr.addressable_shards):
                idx = _index_to_json(shard.index, arr.shape)
                # skip duplicate replicas: only save the first owner
                if any(s["index"] == idx for s in info["shards"]):
                    continue
                host = np.asarray(shard.data)
                fn = f"leaf_{i}/shard_{len(info['shards'])}.npy"
                info["shards"].append({"file": fn, "index": idx})
                work.append((tmp / fn, host))
        else:
            host = np.asarray(jax.device_get(arr))
            fn = f"leaf_{i}.npy"
            info["file"] = fn
            work.append((tmp / fn, host))
        manifest["leaves"].append(info)
    return manifest, work


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

    out = []
    for i, (leaf, info) in enumerate(zip(leaves, manifest["leaves"])):
        if "file" in info:
            host = np.load(d / info["file"])
        else:
            host = np.zeros(info["shape"], dtype=info["dtype"])
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

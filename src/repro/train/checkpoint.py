"""Sharded checkpointing: save/restore/resume with atomic rotation.

Layout per step: ``<dir>/step_<n>/arrays.npz`` + ``manifest.json``
(pytree paths, shapes, dtypes, step, wall time). Writes go to a temp dir
then ``rename`` — a preempted save never corrupts the latest checkpoint
(fault-tolerance contract). ``AsyncCheckpointer`` moves serialization off
the training thread; ``CheckpointManager`` rotates old steps.

On a multi-host cluster each process saves its addressable shards under
``host_<i>/`` and restore reassembles per the current sharding — the
single-process container exercises the same code path with one host dir.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import threading
import time
from typing import Any, Optional

import jax
import numpy as np


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        arr = np.asarray(leaf)
        if arr.dtype.kind == "V":      # bfloat16: npz can't store void16
            arr = np.asarray(jax.numpy.asarray(leaf).astype("float32"))
        flat[key] = arr
    return flat


def save_checkpoint(ckpt_dir: str | pathlib.Path, step: int, state: Any,
                    host_id: int = 0) -> pathlib.Path:
    """Save one host's shards for ``step``; safe under concurrent hosts.

    Each host stages into its own ``.tmp_step_<n>_<host>`` dir and then
    publishes. The first host to publish renames the whole tmp dir into
    place (atomic); later hosts MERGE their ``host_<i>/`` shard dir into
    the already-published step dir instead of clobbering it — rmtree'ing
    an existing step here would delete the shards every other host already
    wrote for the same step (the multi-host publish race). A host
    re-saving the same step replaces only its own shard dir.
    """
    ckpt_dir = pathlib.Path(ckpt_dir)
    final = ckpt_dir / f"step_{step:08d}"
    tmp = ckpt_dir / f".tmp_step_{step:08d}_{host_id}"
    if tmp.exists():
        shutil.rmtree(tmp)
    (tmp / f"host_{host_id}").mkdir(parents=True)
    flat = _flatten(state)
    np.savez(tmp / f"host_{host_id}" / "arrays.npz", **flat)
    manifest = {
        "step": int(step), "time": time.time(),
        "keys": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                 for k, v in flat.items()},
    }
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    if not final.exists():
        try:
            tmp.rename(final)          # atomic publish (first host wins)
            return final
        except OSError:
            pass                       # another host published first: merge
    # merge: move this host's shard dir into the published step (atomic
    # per-host rename), then fold its keys into the shared manifest
    host_dir = final / f"host_{host_id}"
    if host_dir.exists():              # same host re-saving this step
        shutil.rmtree(host_dir)
    (tmp / f"host_{host_id}").rename(host_dir)
    man_path = final / "manifest.json"
    try:
        merged = json.loads(man_path.read_text())
    except (OSError, json.JSONDecodeError):
        merged = {"step": int(step), "time": manifest["time"], "keys": {}}
    merged["keys"].update(manifest["keys"])
    man_path.write_text(json.dumps(merged))
    shutil.rmtree(tmp, ignore_errors=True)
    return final


def latest_step(ckpt_dir: str | pathlib.Path) -> Optional[int]:
    ckpt_dir = pathlib.Path(ckpt_dir)
    if not ckpt_dir.exists():
        return None
    steps = [int(p.name.split("_")[1]) for p in ckpt_dir.glob("step_*")
             if (p / "manifest.json").exists()]
    return max(steps) if steps else None


def restore_checkpoint(ckpt_dir: str | pathlib.Path, template: Any,
                       step: Optional[int] = None, host_id: int = 0) -> Any:
    """Restore into the structure (and shardings) of ``template``."""
    ckpt_dir = pathlib.Path(ckpt_dir)
    step = latest_step(ckpt_dir) if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    path = ckpt_dir / f"step_{step:08d}" / f"host_{host_id}" / "arrays.npz"
    data = np.load(path)
    leaves_paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    new_leaves = []
    for p, leaf in leaves_paths:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in p)
        arr = data[key]
        target_dtype = leaf.dtype
        val = jax.numpy.asarray(arr).astype(target_dtype)
        if hasattr(leaf, "sharding") and leaf.sharding is not None:
            val = jax.device_put(val, leaf.sharding)
        new_leaves.append(val)
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


class CheckpointManager:
    """Rotating checkpoint manager with optional async saves."""

    def __init__(self, ckpt_dir: str | pathlib.Path, keep: int = 3,
                 every: int = 100, async_save: bool = False):
        self.dir = pathlib.Path(ckpt_dir)
        self.keep = keep
        self.every = every
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None

    def due(self, step: int) -> bool:
        """Whether ``maybe_save`` saves at ``step``: every ``every`` steps."""
        return step % self.every == 0

    def maybe_save(self, step: int, state: Any) -> bool:
        if not self.due(step):
            return False
        self.wait()
        # snapshot to host numpy BEFORE handing to the thread: the training
        # loop may donate/overwrite device buffers for the next step
        snap = jax.tree.map(np.asarray, state)
        if self.async_save:
            self._thread = threading.Thread(
                target=self._save_and_gc, args=(step, snap), daemon=True)
            self._thread.start()
        else:
            self._save_and_gc(step, snap)
        return True

    def _save_and_gc(self, step: int, state: Any) -> None:
        save_checkpoint(self.dir, step, state)
        steps = sorted(int(p.name.split("_")[1])
                       for p in self.dir.glob("step_*"))
        for s in steps[:-self.keep]:
            shutil.rmtree(self.dir / f"step_{s:08d}", ignore_errors=True)

    def wait(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            self._thread.join()

    def restore_latest(self, template: Any) -> tuple[Any, int]:
        step = latest_step(self.dir)
        if step is None:
            return template, 0
        return restore_checkpoint(self.dir, template, step), step

"""Atomic, sharded, asynchronous checkpointing (npz-per-leaf).

Layout:   <dir>/step_000123/ {tree.json, leaf_00000.npy, ...}
Atomicity: write to ``step_N.tmp`` then ``os.rename`` (POSIX-atomic).
Async:     a snapshot is taken synchronously (device->host copy), the
           file write happens on a daemon thread; ``wait()`` joins.
Keep-N:    oldest complete checkpoints beyond ``keep`` are deleted.
Restore:   leaves are ``jax.device_put`` against target shardings, so a
           checkpoint written on one mesh restores onto any other
           (elastic re-meshing = restore with new shardings).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional

import numpy as np

import jax


def _flatten(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def save(directory: str, step: int, tree: Any, *, blocking: bool = True
         ) -> Optional[threading.Thread]:
    """Write ``tree`` at ``<directory>/step_{step:08d}`` atomically."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + ".tmp"
    leaves, treedef = _flatten(tree)
    # synchronous device->host snapshot (cheap vs the file write)
    host_leaves = [np.asarray(x) for x in leaves]
    spec = {"n_leaves": len(host_leaves), "treedef": str(treedef),
            "step": step}

    def _write():
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        for i, a in enumerate(host_leaves):
            np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"), a)
        with open(os.path.join(tmp, "tree.json"), "w") as f:
            json.dump(spec, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)

    if blocking:
        _write()
        return None
    t = threading.Thread(target=_write, daemon=True)
    t.start()
    return t


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        if name.startswith("step_") and not name.endswith(".tmp") \
                and os.path.exists(os.path.join(directory, name, "tree.json")):
            steps.append(int(name[5:]))
    return max(steps) if steps else None


def restore(directory: str, step: int, tree_like: Any,
            shardings: Any = None) -> Any:
    """Load a checkpoint into the structure of ``tree_like``.

    ``shardings``: optional matching pytree of jax.sharding.Sharding —
    pass the CURRENT mesh's shardings to restore elastically onto a
    different device count than the checkpoint was written from.
    """
    path = os.path.join(directory, f"step_{step:08d}")
    leaves_like, treedef = _flatten(tree_like)
    host = [np.load(os.path.join(path, f"leaf_{i:05d}.npy"))
            for i in range(len(leaves_like))]
    for a, like in zip(host, leaves_like):
        if tuple(a.shape) != tuple(np.shape(like)):
            raise ValueError(
                f"checkpoint leaf shape {a.shape} != expected "
                f"{np.shape(like)}")
    if shardings is None:
        out = [jax.device_put(a) for a in host]
    else:
        flat_sh = treedef.flatten_up_to(shardings)
        out = [jax.device_put(a, s) for a, s in zip(host, flat_sh)]
    return treedef.unflatten(out)


class CheckpointManager:
    """save-every-N + keep-last-K + async writes + resume-from-latest."""

    def __init__(self, directory: str, *, save_every: int = 100,
                 keep: int = 3, blocking: bool = False):
        self.directory = directory
        self.save_every = save_every
        self.keep = keep
        self.blocking = blocking
        self._thread: Optional[threading.Thread] = None

    def maybe_save(self, step: int, tree: Any, *, force: bool = False):
        if not force and (step == 0 or step % self.save_every):
            return False
        self.wait()
        self._thread = save(self.directory, step, tree,
                            blocking=self.blocking)
        self._gc(step)
        return True

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self, writing: int):
        # called right after the write of step ``writing`` STARTED: keep
        # (keep-1) other checkpoints so that write completes the keep-N
        # set, whether or not it has already finished
        if not os.path.isdir(self.directory) or not self.keep:
            return
        steps = sorted(s for s in (
            int(n[5:]) for n in os.listdir(self.directory)
            if n.startswith("step_") and not n.endswith(".tmp"))
            if s != writing)
        cut = max(self.keep - 1, 1)
        for s in steps[:-cut]:
            shutil.rmtree(os.path.join(
                self.directory, f"step_{s:08d}"), ignore_errors=True)

    def restore_latest(self, tree_like: Any, shardings: Any = None):
        self.wait()
        step = latest_step(self.directory)
        if step is None:
            return None, None
        return step, restore(self.directory, step, tree_like, shardings)

"""Checkpointing: async save, retention, atomic commit, restore.

Design (the ried side of Two-Chains: resident state driven to a process):

  * A checkpoint is a directory ``step_<N>/`` holding one ``arrays.npz``
    (flattened pytree leaves keyed by path) + ``meta.json`` (treedef paths,
    step, config json, wall time). A ``COMMIT`` marker file makes the save
    atomic — restore ignores uncommitted directories, so a host failure
    mid-save never corrupts the latest checkpoint.
  * ``save`` is asynchronous: leaves are fetched to host (blocking only on
    device->host copy), then serialized on a background thread so the train
    loop resumes immediately — checkpoint I/O overlaps the next steps.
  * Retention keeps the newest ``keep`` committed checkpoints.
  * ``restore`` places leaves back onto the mesh with the provided shardings
    (``jax.device_put`` with NamedSharding — works across mesh shapes, which
    is what ``checkpoint.elastic`` builds on).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

PyTree = Any

_COMMIT = "COMMIT"


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(p))
    return "/".join(out)


def flatten_with_paths(tree: PyTree) -> Tuple[List[Tuple[str, Any]], Any]:
    leaves, treedef = jax.tree.flatten_with_path(tree)
    return [(_path_str(p), leaf) for p, leaf in leaves], treedef


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Newest committed step in ``ckpt_dir`` (None if no valid checkpoint)."""
    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_") and os.path.exists(
                os.path.join(ckpt_dir, name, _COMMIT)):
            try:
                steps.append(int(name.split("_", 1)[1]))
            except ValueError:
                continue
    return max(steps) if steps else None


def restore(ckpt_dir: str, step: int, template: PyTree,
            shardings: Optional[PyTree] = None) -> PyTree:
    """Load ``step_<step>`` into the structure of ``template``.

    ``shardings``: optional NamedSharding tree — leaves are device_put with
    it (sharded placement; used by elastic restore onto a different mesh).
    """
    d = os.path.join(ckpt_dir, f"step_{step}")
    if not os.path.exists(os.path.join(d, _COMMIT)):
        raise FileNotFoundError(f"no committed checkpoint at {d}")
    with np.load(os.path.join(d, "arrays.npz")) as z:
        data = {k: z[k] for k in z.files}
    pairs, treedef = flatten_with_paths(template)
    shard_leaves = (jax.tree.leaves(shardings) if shardings is not None
                    else [None] * len(pairs))
    out = []
    for (path, leaf), sh in zip(pairs, shard_leaves):
        arr = data[path]
        if hasattr(leaf, "dtype"):
            arr = arr.astype(leaf.dtype)
        out.append(jax.device_put(arr, sh) if sh is not None else arr)
    return jax.tree.unflatten(treedef, out)


class CheckpointManager:
    """Async checkpoint writer with retention."""

    def __init__(self, ckpt_dir: str, *, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        os.makedirs(ckpt_dir, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    # -- save ------------------------------------------------------------------
    def save(self, step: int, tree: PyTree, *, meta: Optional[Dict] = None,
             blocking: bool = False) -> None:
        """Snapshot ``tree`` at ``step``. Device->host copy happens here;
        serialization happens on a background thread unless ``blocking``."""
        self.wait()  # one in-flight save at a time
        pairs, _ = flatten_with_paths(tree)
        host = [(p, np.asarray(leaf)) for p, leaf in pairs]
        info = dict(meta or {}, step=step, time=time.time())

        def write():
            try:
                final = os.path.join(self.ckpt_dir, f"step_{step}")
                tmp = final + ".tmp"
                shutil.rmtree(tmp, ignore_errors=True)
                shutil.rmtree(final, ignore_errors=True)
                os.makedirs(tmp)
                np.savez(os.path.join(tmp, "arrays.npz"), **dict(host))
                with open(os.path.join(tmp, "meta.json"), "w") as f:
                    json.dump(info, f)
                with open(os.path.join(tmp, _COMMIT), "w") as f:
                    f.write(str(step))
                os.rename(tmp, final)
                self._retain()
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        if blocking:
            write()
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()

    def wait(self) -> None:
        """Block until the in-flight save (if any) commits."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            e, self._error = self._error, None
            raise e

    # -- retention --------------------------------------------------------------
    def _retain(self) -> None:
        steps = sorted(
            int(n.split("_", 1)[1]) for n in os.listdir(self.ckpt_dir)
            if n.startswith("step_") and not n.endswith(".tmp")
            and os.path.exists(os.path.join(self.ckpt_dir, n, _COMMIT)))
        for s in steps[:-self.keep] if self.keep > 0 else []:
            shutil.rmtree(os.path.join(self.ckpt_dir, f"step_{s}"),
                          ignore_errors=True)

    # -- restore ------------------------------------------------------------------
    def restore_latest(self, template: PyTree,
                       shardings: Optional[PyTree] = None
                       ) -> Tuple[Optional[int], Optional[PyTree]]:
        step = latest_step(self.ckpt_dir)
        if step is None:
            return None, None
        return step, restore(self.ckpt_dir, step, template, shardings)

"""Step-tagged atomic checkpointing for restart-after-failure.

Requirements at 1000+-node scale (DESIGN.md §5):
  * atomic: write to a temp dir, fsync, rename -- a preempted save never
    corrupts the latest good checkpoint;
  * self-describing: a manifest records pytree structure, dtypes, mesh shape
    and the data-pipeline step so restore needs no out-of-band state;
  * elastic: leaves are stored UNSHARDED (gathered) in this single-host
    container; restore re-shards onto whatever mesh the surviving slice
    provides (checkpoint/elastic.py).  On a real pod each host would write
    its shard (tensorstore-style); the manifest format already carries the
    mesh so that swap is local to this module;
  * async-capable: ``CheckpointManager(save_async=True)`` snapshots to host
    memory synchronously (cheap) and writes in a background thread so the
    train loop is not blocked by the filesystem.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_MANIFEST = "manifest.json"
_ARRAYS = "arrays.npz"


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ["/".join(str(k) for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def save_checkpoint(directory: str, step: int, state: Any, extra: Optional[dict] = None):
    """Atomic save of an arbitrary pytree under ``directory/step_<N>``."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:08d}")
    paths, leaves, _ = _flatten_with_paths(state)
    arrays = {}
    dtypes = {}
    for p, leaf in zip(paths, leaves):
        arr = np.asarray(jax.device_get(leaf))
        # bf16 is not a numpy-native dtype for npz portability: view as u16
        if arr.dtype == jnp.bfloat16:
            dtypes[p] = "bfloat16"
            arr = arr.view(np.uint16)
        else:
            dtypes[p] = str(arr.dtype)
        arrays[p] = arr
    tmp = tempfile.mkdtemp(dir=directory, prefix=".tmp_save_")
    try:
        with open(os.path.join(tmp, _ARRAYS), "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        manifest = {
            "step": step,
            "paths": paths,
            "dtypes": dtypes,
            "extra": extra or {},
        }
        with open(os.path.join(tmp, _MANIFEST), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return final


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = [
        int(d.split("_")[1])
        for d in os.listdir(directory)
        if d.startswith("step_") and os.path.isdir(os.path.join(directory, d))
    ]
    return max(steps) if steps else None


def restore_checkpoint(
    directory: str, like: Any, step: Optional[int] = None
) -> Tuple[Any, int, dict]:
    """Restore into the structure of ``like`` (a pytree of arrays/specs)."""
    step = step if step is not None else latest_step(directory)
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {directory}")
    d = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(d, _MANIFEST)) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(d, _ARRAYS))
    paths, _, treedef = _flatten_with_paths(like)
    if paths != manifest["paths"]:
        missing = set(manifest["paths"]) ^ set(paths)
        raise ValueError(f"checkpoint/pytree structure mismatch: {sorted(missing)[:5]}")
    leaves = []
    for p in paths:
        arr = data[p]
        if manifest["dtypes"][p] == "bfloat16":
            arr = arr.view(jnp.bfloat16)
        leaves.append(arr)
    return jax.tree.unflatten(treedef, leaves), step, manifest.get("extra", {})


class CheckpointManager:
    """Rolling checkpoints with optional async writes and retention."""

    def __init__(
        self,
        directory: str,
        keep: int = 3,
        save_async: bool = False,
    ):
        self.directory = directory
        self.keep = keep
        self.save_async = save_async
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def save(self, step: int, state: Any, extra: Optional[dict] = None):
        self.wait()
        # Snapshot to host RAM synchronously; device buffers may be donated
        # by the next step.
        snap = jax.tree.map(lambda a: np.asarray(jax.device_get(a)), state)

        def work():
            try:
                save_checkpoint(self.directory, step, snap, extra)
                self._gc()
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        if self.save_async:
            self._thread = threading.Thread(target=work, daemon=True)
            self._thread.start()
        else:
            work()
            self.wait()

    def restore(self, like: Any, step: Optional[int] = None):
        return restore_checkpoint(self.directory, like, step)

    def _gc(self):
        steps = sorted(
            int(d.split("_")[1])
            for d in os.listdir(self.directory)
            if d.startswith("step_")
        )
        for s in steps[: -self.keep]:
            shutil.rmtree(
                os.path.join(self.directory, f"step_{s:08d}"), ignore_errors=True
            )

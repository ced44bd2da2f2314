"""Checkpointing: async, atomic, retained, reshardable.

Layout: <dir>/step_<N>/arrays.npz + meta.json, written to a tmp dir and
atomically renamed — a partially-written checkpoint is never visible.
Restore fills a "like" tree (from jax.eval_shape) by path, optionally
device_put with new shardings — so a checkpoint taken on one mesh restores
onto any other (elastic scaling).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.common.tree import iter_paths, tree_set


def _path_key(path) -> str:
    return "/".join(str(p) for p in path)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self.save_seconds = 0.0

    # -------------------------------------------------------------- save

    def save(self, step: int, tree: Any, blocking: bool = True,
             extra_meta: Optional[dict] = None) -> None:
        """Snapshot to host then write. blocking=False -> background thread
        (async checkpointing: train continues while IO happens)."""
        host = {(_path_key(p)): np.asarray(jax.device_get(leaf))
                for p, leaf in iter_paths(tree)}
        # npz stores bfloat16 as raw 2-byte records; the dtype names let
        # restore view them back
        meta = {"step": step, "time": time.time(),
                "dtypes": {k: v.dtype.name for k, v in host.items()},
                **(extra_meta or {})}
        self.wait()
        if blocking:
            self._write(step, host, meta)
        else:
            self._thread = threading.Thread(
                target=self._write, args=(step, host, meta), daemon=True)
            self._thread.start()

    def _write(self, step: int, host: dict, meta: dict) -> None:
        t0 = time.perf_counter()
        final = os.path.join(self.directory, f"step_{step:08d}")
        tmp = final + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        np.savez(os.path.join(tmp, "arrays.npz"), **host)
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)
        shutil.rmtree(final, ignore_errors=True)
        os.rename(tmp, final)
        self._gc()
        self.save_seconds = time.perf_counter() - t0

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)

    # ----------------------------------------------------------- restore

    def all_steps(self) -> list:
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and not name.endswith(".tmp"):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, like: Any, step: Optional[int] = None,
                shardings: Any = None) -> Any:
        """Fill ``like``-structured tree from checkpoint. ``shardings``
        (same structure, or None) controls placement — pass shardings for
        a *different* mesh to reshard on restore."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        path = os.path.join(self.directory, f"step_{step:08d}", "arrays.npz")
        data = np.load(path)
        dtypes = self.meta(step).get("dtypes", {})
        tree = like
        for p, leaf in iter_paths(like):
            key = _path_key(p)
            arr = data[key]
            if arr.dtype.kind == "V":
                arr = arr.view(jax.numpy.dtype(dtypes[key]))
            if shardings is not None:
                shard = shardings
                for k in p:
                    if isinstance(shard, dict) or isinstance(shard, (list, tuple)):
                        shard = shard[k]
                arr = jax.device_put(arr, shard)
            else:
                arr = jax.numpy.asarray(arr, dtype=leaf.dtype)
            tree = tree_set(tree, p, arr)
        return tree

    def meta(self, step: Optional[int] = None) -> dict:
        if step is None:
            step = self.latest_step()
        with open(os.path.join(self.directory, f"step_{step:08d}",
                               "meta.json")) as f:
            return json.load(f)

    # ------------------------------------------------- sidecar documents
    # Artifact bundles (PrunedArtifact) keep JSON documents and auxiliary
    # array files next to the weight checkpoint; writes are atomic
    # (tmp + rename) like the checkpoint itself.

    def save_json(self, name: str, obj: Any) -> None:
        path = os.path.join(self.directory, name)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(obj, f, indent=2)
        os.replace(tmp, path)

    def load_json(self, name: str) -> Any:
        with open(os.path.join(self.directory, name)) as f:
            return json.load(f)

    def save_arrays(self, name: str, arrays: dict) -> None:
        path = os.path.join(self.directory, name)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, path)

    def load_arrays(self, name: str) -> dict:
        with np.load(os.path.join(self.directory, name)) as data:
            return {k: data[k] for k in data.files}

    def has(self, name: str) -> bool:
        return os.path.exists(os.path.join(self.directory, name))

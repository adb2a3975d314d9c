"""Fault-tolerant checkpointing: atomic, versioned, async, reshard-on-restore.

Layout:  <dir>/step_<N>/<leaf-path>.npy + manifest.json
Writes go to ``step_<N>.tmp`` and are renamed into place only after every
leaf and the manifest have been fsync'd — a preempted writer can never
produce a half checkpoint that restore would pick up (restore scans only
completed dirs).  ``keep`` old checkpoints are retained.

``save_async`` snapshots to host memory synchronously (cheap) and writes on a
background thread, so the train loop is blocked only for the device→host
copy.  ``restore`` takes a *target* tree (arrays or ShapeDtypeStructs with
shardings) and device_puts each leaf onto the target sharding — this is what
makes **elastic restarts** work: a checkpoint written on a 512-chip mesh
restores onto 256 chips (or 1 CPU) by simply passing the new target specs.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import jax
import numpy as np

_LEAF_SEP = "."


def _keystr(path) -> str:
    return jax.tree_util.keystr(path, simple=True, separator=_LEAF_SEP)


def _flatten(tree) -> Dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[_keystr(path)] = leaf
    return flat


class CheckpointManager:
    def __init__(self, directory: str, *, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._lock = threading.Lock()
        self._pending: Optional[Future] = None

    # ---- write -------------------------------------------------------------
    def save(self, step: int, tree) -> str:
        # Drain any in-flight async write first: two writers racing on the
        # same step's tmp dir TOCTOU each other (seen when the final sync
        # save lands on a step save_async already picked up).
        self.wait()
        host = jax.tree_util.tree_map(lambda x: np.asarray(x), tree)
        return self._write(step, host)

    def save_async(self, step: int, tree) -> Future:
        self.wait()
        host = jax.tree_util.tree_map(lambda x: np.asarray(x), tree)  # snapshot
        self._pending = self._pool.submit(self._write, step, host)
        return self._pending

    def wait(self) -> None:
        if self._pending is not None:
            self._pending.result()
            self._pending = None

    def _write(self, step: int, host_tree) -> str:
        final = os.path.join(self.directory, f"step_{step:09d}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat = _flatten(host_tree)
        manifest = {"step": step, "leaves": {}}
        for key, leaf in flat.items():
            arr = np.asarray(leaf)
            fname = re.sub(r"[^A-Za-z0-9_.-]", "_", key) + ".npy"
            np.save(os.path.join(tmp, fname), arr)
            manifest["leaves"][key] = {"file": fname, "shape": list(arr.shape),
                                       "dtype": str(arr.dtype)}
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        with self._lock:
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)   # atomic commit
            self._gc()
        return final

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:09d}"),
                          ignore_errors=True)

    # ---- read --------------------------------------------------------------
    def all_steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.directory):
            m = re.fullmatch(r"step_(\d+)", name)
            if m and os.path.exists(os.path.join(self.directory, name,
                                                 "manifest.json")):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, step: int, target):
        """Load into the structure (and shardings) of `target`.

        `target` leaves may be arrays (restored onto their shardings) or
        ShapeDtypeStructs carrying a .sharding (elastic reshard path).
        """
        d = os.path.join(self.directory, f"step_{step:09d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        flat_target = _flatten(target)
        loaded = {}
        for key, tgt in flat_target.items():
            meta = manifest["leaves"].get(key)
            if meta is None:
                raise KeyError(f"checkpoint {step} missing leaf {key!r}")
            arr = np.load(os.path.join(d, meta["file"]))
            sharding = getattr(tgt, "sharding", None)
            if sharding is not None and not isinstance(
                    sharding, jax.sharding.SingleDeviceSharding):
                loaded[key] = jax.device_put(arr.astype(tgt.dtype), sharding)
            else:
                loaded[key] = jax.numpy.asarray(arr.astype(tgt.dtype))
        # reassemble in target's treedef order
        paths, treedef = jax.tree_util.tree_flatten_with_path(target)
        leaves = [loaded[_keystr(p)] for p, _ in paths]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def restore_latest(self, target):
        step = self.latest_step()
        if step is None:
            return None, None
        return step, self.restore(step, target)

"""Fault-tolerant checkpointing.

* Atomic:      every file is written to a temp name, fsync'd, renamed;
               the checkpoint directory is only committed by renaming a
               MANIFEST file last, so a crash mid-save can never leave a
               readable-but-corrupt checkpoint.
* Async:       ``save_async`` snapshots arrays to host and writes on a
               background thread — training continues into the next step.
* Mesh-agnostic: arrays are saved as full (unsharded) logical tensors with
               a tree manifest; ``restore`` reshards onto whatever mesh the
               job restarts with (elastic scaling: 512 -> 256 chips resumes
               fine).
* Keep-N GC + ``latest_step`` discovery for auto-resume after failure.
* All writes go through buffered Python file objects, so a profiling
  session records them on the STDIO layer (paper §IV-D / Fig 6).
* Traced:      ``ckpt.*`` spans (``jax.profiler.TraceAnnotation``) name
               each phase on the profiler's timeline: on the caller's
               thread ``ckpt.wait_writer`` and ``ckpt.snapshot``; per
               save ``ckpt.write`` holding ``ckpt.serialize`` (per leaf),
               ``ckpt.file_write``, ``ckpt.fsync`` and ``ckpt.commit``.
"""
from __future__ import annotations

import json
import os
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

MANIFEST = "MANIFEST.json"


def _tree_paths(tree) -> List[tuple]:
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    out = []
    for path, leaf in leaves:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        out.append((name, leaf))
    return out


def _write_atomic(path: str, data: bytes) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        with TraceAnnotation("ckpt.file_write"):
            f.write(data)
            f.flush()
        with TraceAnnotation("ckpt.fsync"):
            os.fsync(f.fileno())
    os.rename(tmp, path)


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        # writer throttle (repro.tune throttle-checkpoint actions):
        # async saves within min_interval_s of the previous save's call
        # are skipped without waiting on the writer; sync saves always
        # land (the final save must commit)
        self.min_interval_s = 0.0
        self.throttle_skipped = 0
        self._last_save_t: Optional[float] = None

    def set_throttle(self, min_interval_s: float) -> float:
        """Space async saves at least ``min_interval_s`` apart (0
        disables).  Returns the previous interval."""
        prev = self.min_interval_s
        self.min_interval_s = max(float(min_interval_s), 0.0)
        return prev

    # ------------------------------------------------------------------ save
    def save(self, step: int, tree: Dict[str, Any],
             extra: Optional[dict] = None) -> str:
        """Synchronous atomic save.  ``tree`` is a pytree of arrays."""
        if threading.current_thread() is not self._thread:
            self._last_save_t = time.monotonic()  # save_async stamps its own
        with TraceAnnotation("ckpt.write"):
            ckpt_dir = os.path.join(self.directory, f"step_{step:010d}")
            stage = ckpt_dir + ".staging"
            os.makedirs(stage, exist_ok=True)
            entries = []
            for name, leaf in _tree_paths(tree):
                with TraceAnnotation("ckpt.serialize"):
                    arr = np.asarray(jax.device_get(leaf))
                    payload = _npy_bytes(arr)
                    crc = zlib.crc32(payload) & 0xFFFFFFFF
                fname = name.replace("/", ".") + ".npy"
                _write_atomic(os.path.join(stage, fname), payload)
                entries.append({"name": name, "file": fname,
                                "shape": list(arr.shape),
                                "dtype": str(arr.dtype), "crc32": crc})
            with TraceAnnotation("ckpt.commit"):
                manifest = {"step": step, "entries": entries,
                            "extra": extra or {}, "format": 1}
                _write_atomic(os.path.join(stage, MANIFEST),
                              json.dumps(manifest, indent=1).encode())
                os.rename(stage, ckpt_dir)          # commit
                self._gc()
        return ckpt_dir

    def save_async(self, step: int, tree,
                   extra: Optional[dict] = None) -> bool:
        """Snapshot to host now; write on a background thread.  Returns
        False, at once and without waiting on the writer, when the
        throttle skipped this save (the last save was called too
        recently; one still being written always is)."""
        now = time.monotonic()
        if self.min_interval_s > 0 and self._recent(now):
            self.throttle_skipped += 1
            return False
        self.wait()                          # one in flight at a time
        self._last_save_t = now
        with TraceAnnotation("ckpt.snapshot"):
            host_tree = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), tree)

        def work():
            try:
                self.save(step, host_tree, extra)
            except BaseException as e:      # noqa: BLE001
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()
        return True

    def _recent(self, now: float) -> bool:
        writing = self._thread is not None and self._thread.is_alive()
        return writing or (self._last_save_t is not None
                           and now - self._last_save_t < self.min_interval_s)

    def wait(self) -> None:
        if self._thread is not None:
            with TraceAnnotation("ckpt.wait_writer"):
                self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    # --------------------------------------------------------------- restore
    def latest_step(self) -> Optional[int]:
        steps = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and not name.endswith(".staging") \
                    and os.path.exists(os.path.join(self.directory, name,
                                                    MANIFEST)):
                steps.append(int(name[5:]))
        return max(steps) if steps else None

    def restore(self, step: Optional[int] = None,
                shardings=None, target_tree=None) -> tuple:
        """Returns (tree, manifest_extra).  ``shardings``: optional pytree
        of NamedSharding to reshard onto (mesh-agnostic restore);
        ``target_tree``: pytree prototype defining the structure."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.directory}")
        ckpt_dir = os.path.join(self.directory, f"step_{step:010d}")
        with open(os.path.join(ckpt_dir, MANIFEST)) as f:
            manifest = json.load(f)
        arrays: Dict[str, np.ndarray] = {}
        for e in manifest["entries"]:
            path = os.path.join(ckpt_dir, e["file"])
            with open(path, "rb") as f:
                payload = f.read()
            if zlib.crc32(payload) & 0xFFFFFFFF != e["crc32"]:
                raise IOError(f"checkpoint corruption in {path}")
            arrays[e["name"]] = _npy_from_bytes(payload)
        if target_tree is not None:
            named = _tree_paths(target_tree)
            leaves = [arrays[n] for n, _ in named]
            tree = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(target_tree), leaves)
        else:
            tree = arrays
        if shardings is not None:
            tree = jax.tree.map(
                lambda a, s: jax.device_put(a, s), tree, shardings)
        return tree, manifest["extra"]

    # ------------------------------------------------------------------- gc
    def _gc(self) -> None:
        steps = sorted(s for s in (
            int(n[5:]) for n in os.listdir(self.directory)
            if n.startswith("step_") and not n.endswith(".staging")))
        for s in steps[:-self.keep] if self.keep > 0 else []:
            d = os.path.join(self.directory, f"step_{s:010d}")
            try:
                for f in os.listdir(d):
                    try:
                        os.remove(os.path.join(d, f))
                    except FileNotFoundError:
                        pass
                os.rmdir(d)
            except (FileNotFoundError, OSError):
                pass        # concurrent GC from an async save — benign


def _npy_bytes(arr: np.ndarray) -> bytes:
    import io
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return buf.getvalue()


def _npy_from_bytes(data: bytes) -> np.ndarray:
    import io
    return np.load(io.BytesIO(data), allow_pickle=False)

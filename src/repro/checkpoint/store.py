"""Versioned sharded checkpoint store.

Layout::

    <dir>/step_<N>/manifest.json       # leaf paths, shapes, dtypes, version
    <dir>/step_<N>/<leaf-hash>.npy     # one array per pytree leaf
    <dir>/LATEST                       # atomic pointer (rename-committed)

Writes are crash-safe: the step directory is written under a temp name and
atomically renamed, then LATEST is updated by rename — a torn write can
never be observed, mirroring the "no object observed mid-transaction"
guarantee the control plane gives in-process. Save runs inside an
*irrevocable read-only* OptSVA-CF transaction when coordinated through
``repro.txstore`` (file I/O must never be re-executed; paper §2.4).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
import threading
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

Params = Any


def _leaf_key(path: Tuple) -> str:
    names = [p.key if hasattr(p, "key") else str(getattr(p, "idx", p))
             for p in path]
    return "/".join(names)


class CheckpointStore:
    def __init__(self, directory: str):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)

    # ------------------------------------------------------------------ #
    def save(self, tree: Params, step: int) -> str:
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        tmp = Path(tempfile.mkdtemp(dir=self.dir, prefix=f".tmp_step_{step}_"))
        manifest: Dict[str, Any] = {"step": step, "leaves": {}}
        try:
            for path, leaf in leaves:
                key = _leaf_key(path)
                fname = hashlib.sha1(key.encode()).hexdigest()[:16] + ".npy"
                arr = np.asarray(leaf)
                np.save(tmp / fname, arr)
                manifest["leaves"][key] = {
                    "file": fname, "shape": list(arr.shape),
                    "dtype": str(arr.dtype)}
            (tmp / "manifest.json").write_text(json.dumps(manifest, indent=2))
            final = self.dir / f"step_{step}"
            if final.exists():
                shutil.rmtree(final)
            os.rename(tmp, final)                      # atomic commit
            self._set_latest(step)
            return str(final)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    def _set_latest(self, step: int) -> None:
        ptr = self.dir / "LATEST"
        tmp = self.dir / ".LATEST.tmp"
        tmp.write_text(str(step))
        os.rename(tmp, ptr)                            # atomic pointer swap

    # ------------------------------------------------------------------ #
    def latest_step(self) -> Optional[int]:
        ptr = self.dir / "LATEST"
        if not ptr.exists():
            return None
        step = int(ptr.read_text().strip())
        if not (self.dir / f"step_{step}" / "manifest.json").exists():
            return None  # torn directory (crash between renames): ignore
        return step

    def restore(self, template: Params, step: Optional[int] = None,
                *, shardings: Optional[Params] = None) -> Tuple[Params, int]:
        """Load into the template's treedef; optionally device_put with new
        shardings (elastic restore onto a different mesh)."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError("no checkpoint available")
        d = self.dir / f"step_{step}"
        manifest = json.loads((d / "manifest.json").read_text())
        leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
        out: List[Any] = []
        sh_leaves = (jax.tree_util.tree_leaves(shardings)
                     if shardings is not None else [None] * len(leaves))
        for (path, leaf), sh in zip(leaves, sh_leaves):
            key = _leaf_key(path)
            meta = manifest["leaves"][key]
            arr = np.load(d / meta["file"])
            assert list(arr.shape) == meta["shape"]
            if sh is not None:
                out.append(jax.device_put(arr, sh))
            else:
                out.append(jax.numpy.asarray(arr))
        tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), out)
        return tree, step

    def gc(self, keep: int = 3) -> None:
        steps = sorted(int(p.name.split("_")[1])
                       for p in self.dir.glob("step_*"))
        for s in steps[:-keep]:
            shutil.rmtree(self.dir / f"step_{s}", ignore_errors=True)


class CheckpointError(RuntimeError):
    """A submitted checkpoint failed to save or did not finish in time."""


class AsyncCheckpointer:
    """Background checkpoint writer fed by transactional snapshots.

    ``submit`` is called with an already-consistent snapshot (taken by the
    txstore's irrevocable read-only transaction); the file I/O happens on
    this thread so the trainer never blocks on disk.
    """

    def __init__(self, store: CheckpointStore,
                 on_done: Optional[Callable[[int, str], None]] = None):
        self.store = store
        self.on_done = on_done
        self._lock = threading.Lock()
        self._pending: Optional[Tuple[Params, int]] = None
        self._busy = False                 # a save is in flight on the thread
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._idle = threading.Condition(self._lock)
        self.saved: List[int] = []
        self.errors: List[str] = []
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="async-ckpt")
        self._thread.start()

    def submit(self, tree: Params, step: int) -> None:
        with self._lock:
            self._pending = (tree, step)   # newest wins; older snap dropped
        self._wake.set()

    def _loop(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(timeout=0.2)
            self._wake.clear()
            with self._lock:
                job, self._pending = self._pending, None
                self._busy = job is not None
            if job is None:
                continue
            tree, step = job
            try:
                path = self.store.save(tree, step)
                self.saved.append(step)
                if self.on_done:
                    self.on_done(step, path)
            except BaseException as e:  # noqa: BLE001
                self.errors.append(repr(e))
            finally:
                with self._lock:
                    self._busy = False
                    self._idle.notify_all()

    def drain(self, timeout: float = 600.0) -> None:
        """Block until every submitted snapshot is fully on disk — i.e. no
        job is pending AND no save is in flight (a drain that returns while
        the last save is mid-write lets callers observe the previous
        LATEST pointer).

        Raises :class:`CheckpointError` if the wait times out or any save
        has failed, so a run never ends looking healthy without its
        checkpoints.
        """
        self._wake.set()
        with self._lock:
            idle = self._idle.wait_for(
                lambda: self._pending is None and not self._busy,
                timeout=timeout)
        if not idle:
            raise CheckpointError(
                f"checkpoint not on disk after {timeout:.0f}s")
        if self.errors:
            raise CheckpointError(
                f"{len(self.errors)} checkpoint save(s) failed: "
                f"{self.errors[0]}")

    def stop(self) -> None:
        try:
            self.drain()
        finally:
            self._stop.set()
            self._wake.set()
            self._thread.join(timeout=10.0)

"""Transactional versioned training-state store (DESIGN.md §2.1).

The control plane of the training runtime, synchronized by **OptSVA-CF**
(``repro.core``). Cluster state — parameters, optimizer state, the data
cursor, checkpoint metadata — lives in shared objects homed on registry
nodes; every actor runs transactions against them:

* the **trainer** commits each step(-group) as an *update* transaction with
  suprema 1 per object (one ``set`` per step);
* the **checkpointer** is an *irrevocable read-only* transaction: per paper
  §2.7 the snapshot is taken by the executor thread the moment the access
  condition passes and the objects are released immediately — the trainer
  blocks only for the buffer copy, never for the checkpoint I/O; and per
  §2.4 irrevocability means the file write can never be re-executed by a
  cascade;
* **evaluators** are read-only transactions (same asynchronous buffering);
* **elastic rescale** events are update transactions that swap shardings.

The paper's guarantees carry over directly: no torn reads (a checkpoint
snapshot is a consistent version cut across params/opt/cursor), no
writer starvation, deadlock freedom, and crashed actors roll back via the
transaction monitor (§3.4).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

from repro.core import (Mode, Registry, SharedObject, Transaction,
                        TransactionMonitor, access)
from repro.obs import txtrace as _txtrace


class StateCell:
    """A shared object holding one piece of cluster state.

    ``set`` is a pure WRITE (never reads), so trainer commits go through the
    log buffer without synchronizing with concurrent snapshot readers until
    apply time (§2.6). jax arrays are immutable, so snapshot copies are
    reference copies — cheap.
    """

    def __init__(self, value: Any = None, version: int = 0):
        self.value = value
        self.version = version

    @access(Mode.READ)
    def get(self):
        return self.value

    @access(Mode.READ)
    def get_version(self) -> int:
        return self.version

    @access(Mode.WRITE)
    def set(self, value, version: int) -> None:
        self.value = value
        self.version = version

    @access(Mode.UPDATE)
    def bump(self, fn: Callable[[Any], Any]) -> Any:
        self.value = fn(self.value)
        self.version += 1
        return self.value

    def __deepcopy__(self, memo):
        # jax arrays are immutable: snapshot = reference copy of the pytree
        return StateCell(self.value, self.version)

    def __tx_snapshot__(self) -> "StateCell":
        # Snapshot protocol (buffers.py): same reference-copy rationale, but
        # O(1) with no deepcopy dispatch on the checkpoint/read-buffer path.
        return StateCell(self.value, self.version)


class VersionedStateStore:
    """Named state cells + transaction factories for the runtime actors."""

    CELLS = ("params", "opt", "data_cursor", "ckpt_meta")
    #: the txtrace site of the cells' transaction events (``vwait``,
    #: ``lw_apply``, ...), as a node server stamps its own objects; the
    #: trainer's spans land there too (``repro.runtime.profiling``)
    SITE = "trainer"

    def __init__(self, *, monitor_timeout: float = 30.0):
        self.registry = Registry()
        self.node = self.registry.add_node("trainer-host")
        self.cells: Dict[str, SharedObject] = {}
        for name in self.CELLS:
            self.cells[name] = self.registry.bind(
                name, StateCell(), node=self.node)
            self.cells[name].header.obs_tracer = _txtrace.tracer(self.SITE)
        self.monitor = TransactionMonitor(self.registry,
                                          timeout=monitor_timeout)
        self.monitor.start()

    def shutdown(self) -> None:
        self.monitor.stop()
        self.registry.shutdown()

    # ------------------------------------------------------------------ #
    # Actor transactions                                                  #
    # ------------------------------------------------------------------ #
    def commit_step(self, params, opt, step: int) -> None:
        """Trainer: publish the post-step state (one write per cell)."""
        t = Transaction(self.registry)
        p = t.writes(self.cells["params"], 1)
        o = t.writes(self.cells["opt"], 1)
        c = t.writes(self.cells["data_cursor"], 1)

        def body(t):
            p.set(params, step)
            o.set(opt, step)
            c.set(step, step)

        t.start(body)

    def snapshot(self, cells: Iterable[str] = ("params", "opt", "data_cursor"),
                 *, irrevocable: bool = True) -> Dict[str, Any]:
        """Checkpointer/evaluator: consistent read-only snapshot.

        Uses the §2.7 asynchronous buffering path: each cell is snapshotted
        and released by the executor as soon as its access condition passes.
        """
        t = Transaction(self.registry, irrevocable=irrevocable)
        proxies = {name: t.reads(self.cells[name], 2) for name in cells}
        out: Dict[str, Any] = {}

        def body(t):
            for name, proxy in proxies.items():
                out[name] = proxy.get()
                out[f"{name}_version"] = proxy.get_version()

        t.start(body)
        return out

    def record_checkpoint(self, step: int, path: str) -> None:
        t = Transaction(self.registry)
        m = t.writes(self.cells["ckpt_meta"], 1)
        t.start(lambda _t: m.set({"step": step, "path": path,
                                  "time": time.time()}, step))

    def latest_checkpoint(self) -> Optional[Dict[str, Any]]:
        snap = self.snapshot(("ckpt_meta",))
        return snap["ckpt_meta"]

    def rescale(self, remap: Callable[[Any], Any]) -> None:
        """Elastic event: atomically re-shard params+opt under one txn."""
        t = Transaction(self.registry)
        p = t.updates(self.cells["params"], 1)
        o = t.updates(self.cells["opt"], 1)

        def body(t):
            p.bump(remap)
            o.bump(remap)

        t.start(body)

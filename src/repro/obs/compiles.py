"""Compilations of the programs a process runs.

One record per function JAX traced to a jaxpr (``"trace"``), per module
it lowered (``"lower"``), and per program the compiler built
(``"compile"``) or loaded from the persistent compile cache (``"load"``):
``(function name, kind, seconds, step, t)``, where ``step`` is the
trainer step open when it happened (``None`` outside ``Trainer.run``'s
traced steps), so a recompile of the train step names the step that paid
for it, and ``t`` is when it ended on the monotonic clock.

The listener that feeds :data:`LOG` from JAX's compile events is
:func:`repro.runtime.profiling.watch_compiles`; once installed it records
every one, whether tracing is on or not: they are rare, off every hot
path, and most happen before any trace starts. This module imports no
JAX.
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

Record = Tuple[str, str, float, Optional[int], float]


class CompileLog:
    """The process's compile records, in the order they happened."""

    def __init__(self) -> None:
        self.records: List[Record] = []
        #: the trainer step open now (set by ``Trainer.run``'s step span)
        self.step: Optional[int] = None

    def record(self, fun: str, kind: str, seconds: float) -> None:
        self.records.append((fun, kind, seconds, self.step, time.monotonic()))

    def between(self, t0: float, t1: float) -> Tuple[int, int, float]:
        """(programs compiled, programs loaded, seconds of all four kinds)
        of the records that ended in [t0, t1). A nested function's trace
        lies inside its caller's, so the seconds are those of the union of
        the records' intervals, each counted once."""
        rs = [r for r in self.records if t0 <= r[4] < t1]
        compiled = sum(1 for r in rs if r[1] == "compile")
        loaded = sum(1 for r in rs if r[1] == "load")
        seconds, reach = 0.0, float("-inf")
        for a, b in sorted((r[4] - r[2], r[4]) for r in rs):
            if b > reach:
                seconds += b - max(a, reach)
                reach = b
        return compiled, loaded, seconds

    def reset(self) -> None:
        self.records.clear()


#: The process-wide log.
LOG = CompileLog()

"""repro.obs — structured tracing + metrics for the OptSVA-CF stack.

Its pieces (DESIGN.md §9):

* :mod:`repro.obs.txtrace` — per-thread ring buffers of binary span
  events, one set per *site* (a node, a client, the ``trainer``): the
  transaction lifecycle, correlated cross-node by ``(txn_uid,
  incarnation, pv)``, and ``Trainer.run``'s step and phase spans;
* :mod:`repro.obs.metrics` — counters + HDR-style histograms (gate wait,
  version wait, version-handoff latency), exposed via the ``stats`` RPC
  and a SIGUSR2 dump;
* :mod:`repro.obs.compiles` — the programs the process compiled or
  loaded from the compile cache, with their seconds (kept whether tracing
  is on or not: compiles are rare and mostly precede any trace);
* :mod:`repro.obs.export` — merges per-site rings into Chrome-trace /
  Perfetto JSON (one track per node, one flow per transaction).

Sites read their own clock: ``time.monotonic`` over TCP and in-process,
simnet's virtual clock under simulation. The trainer's events are put on
a JAX profile's clock by ``repro.runtime.profiling``, which holds
everything here that touches JAX; this package imports none.

Everything else is gated on the single module flag ``txtrace.enabled``
(default off, or the ``REPRO_TRACE`` environment variable): every
instrumentation site in the hot path is ``if txtrace.enabled: ...`` —
one attribute read when tracing is off, no allocation, no locks, no
messages. Enabling tracing never adds protocol messages either (rings
are in-process; export pulls them explicitly), so the simnet exact
message-plan gate holds with tracing on or off.
"""
from . import txtrace, metrics, compiles, export  # noqa: F401

__all__ = ["txtrace", "metrics", "compiles", "export"]

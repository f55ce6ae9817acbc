"""Program spans on the profiler's clock: the bridge from ``repro.obs`` to
``jax.profiler``.

``repro.obs`` imports no JAX; this module is where its events meet the
profiler. The spans record only while ``txtrace.enabled`` is on: callers
check the flag and pass :data:`OFF` otherwise, so a site with tracing off
costs one attribute read. ``Trainer.run`` switches the flag on for its
own length when it starts under a JAX profile (:func:`profiled`), so a
profile of training holds the program's spans with nothing else to set.

* :func:`step_span` / :func:`phase` — one span of ``Trainer.run``: a
  profiler annotation (``StepTraceAnnotation("train", step_num=step)`` for
  the step, ``TraceAnnotation(name, step=step)`` for a phase) and a
  txtrace span of the same name on the :data:`TRAINER` site, carrying the
  step in its ``pv`` field. Trainer thread only.
* :func:`anchor` — the :data:`ANCHOR` annotation, written when a traced
  ``Trainer.run`` starts under a profile, and a txtrace instant holding
  the clock read around it. The profile's event times count from its own
  origin; the anchor is what maps txtrace times onto them
  (``trace_ns = anchor_ns + (t - t_anchor) * 1e9``).
* :func:`watch_compiles` — feeds :data:`repro.obs.compiles.LOG` from JAX's
  trace, lowering and compile events (installed by every ``Trainer``).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List

import jax
from jax import monitoring

from repro.obs import compiles as _compiles
from repro.obs import txtrace as _txtrace
from repro.txstore.store import VersionedStateStore

#: The trainer's site: its spans, and the transaction events of the
#: trainer's store (the store's cells point their headers here).
TRAINER = _txtrace.tracer(VersionedStateStore.SITE)

#: What a site enters when tracing is off.
OFF = contextlib.nullcontext()

STEP = "train"
ANCHOR = "txtrace.anchor"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: JAX's other compile-path events, by the kind the compile log gives them
STAGE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}


@contextlib.contextmanager
def step_span(step: int):
    """One training step (call only under ``txtrace.enabled``); the compile
    log puts what compiles inside it at ``step``."""
    _compiles.LOG.step = step
    t0 = TRAINER.now()
    try:
        with jax.profiler.StepTraceAnnotation(STEP, step_num=step):
            yield
        TRAINER.span(STEP, t0, pv=step)
    finally:
        _compiles.LOG.step = None


@contextlib.contextmanager
def phase(name: str, step: int):
    """One phase of a step (call only under ``txtrace.enabled``)."""
    t0 = TRAINER.now()
    with jax.profiler.TraceAnnotation(name, step=step):
        yield
    TRAINER.span(name, t0, pv=step)


def profiled() -> bool:
    """Whether a JAX profile is being collected now."""
    return jax.profiler.TraceAnnotation.is_enabled()


def anchor() -> None:
    """Write the :data:`ANCHOR` annotation into the running profile, and an
    :data:`ANCHOR` instant on the :data:`TRAINER` site stamped with the
    txtrace clock at the annotation's midpoint. The annotation is written
    twice and the second counts: a thread's first annotation in a profile
    pays for the profiler's set-up on that thread (30-40 us against about
    2), which would blur its midpoint."""
    for _ in range(2):
        t0 = TRAINER.now()
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass
        t1 = TRAINER.now()
    TRAINER.emit(ANCHOR, (t0 + t1) / 2)


def drain() -> List[Dict]:
    """The :data:`TRAINER` site's events (every thread's ring), removed."""
    evs = TRAINER.events()
    TRAINER.reset()
    return evs


_hit = threading.local()
_watching = False


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        _hit.pending = True


def _on_duration(event: str, seconds: float, fun_name: str = "",
                 **_kw) -> None:
    if event in STAGE_EVENTS:
        _compiles.LOG.record(fun_name, STAGE_EVENTS[event], seconds)
    if event != COMPILE_EVENT:
        return
    # a cache hit is reported inside the compile it replaces
    kind = "load" if getattr(_hit, "pending", False) else "compile"
    _hit.pending = False
    _compiles.LOG.record(fun_name, kind, seconds)


def watch_compiles() -> None:
    """Record every later trace, lowering, compile or cache load in the
    compile log. Idempotent."""
    global _watching
    if not _watching:
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True

"""Spans and counters of a sweep, recorded in memory while enabled.

Off by default.  ``enable()`` starts a fresh recording, ``disable()``
stops it and ``records()`` returns what was recorded.  The sweep engine
opens six spans at its layer boundaries:

================== ========================================== ====================
span               where                                      counters
================== ========================================== ====================
``sweep``          ``engine.sweep_results``, the whole body   ``rows``
``sweep.build``    ``engine.build_sweep_batch`` /             ``rows``,
                   ``build_tiering_batch``                    ``steps``,
                                                              ``accesses``
``sweep.build.     each ``Workload.device_trace`` call in     ``workload``,
trace``            those builders                             ``accesses``
``sweep.prep``     argument conversion at the top of          --
                   ``engine.run_traces`` and
                   ``tiering_dyn.prep_dynamic_inputs``
``sweep.program``  dispatch to completion of the device       ``program``,
                   program in ``engine.run_traces`` and       ``backend``,
                   ``tiering_dyn.run_dynamic``                ``row_steps``,
                                                              ``segments``,
                                                              ``vmem_limit_bytes``
                                                              (static also
                                                              ``state_bytes``)
``sweep.timing``   ``machine.time_batch``                     ``rows``
================== ========================================== ====================

``rows`` and ``steps`` are the batch rows and padded scan steps, and
``row_steps`` their product as the program ran it (segment padding
included); ``accesses`` are unpadded trace entries.  ``program`` is
``static`` or ``epoch`` and ``backend`` the implementation that ran it
(``pallas`` or ``reference``, as ``engine.resolve_backend`` chose).
``state_bytes`` is one row's cache state (``CacheParams.state_bytes``)
and ``vmem_limit_bytes`` the scoped VMEM the program's kernel compiled
with (``cache_sim.vmem_limit_bytes``; the epoch kernel's with its page
count), 0 on the scan.  Every
counter comes from shapes the host already knows, never from a device
read.

Each span records its name, its start and end on
``time.perf_counter_ns()`` (the clock a profiler session's
``bench.anchor`` maps onto), its id, its parent's id and the id of the
``sweep`` it belongs to (``None`` outside a sweep), and opens a
``jax.profiler.TraceAnnotation`` of its name, so that a profile shows it
beside the device ops.  A span whose work is on the device blocks on its
outputs (``Span.ready``) before it ends.

While enabled, ``jax.monitoring`` listeners add ``compiles`` (backend
compilations or persistent-cache loads), ``compile_s`` (jaxpr tracing,
lowering to MLIR and backend compilation, which holds a cache load) and
``cache_hits`` / ``cache_misses`` of the persistent compilation cache
to the innermost open span.  With no span open they go to an
``outside`` record, one per stretch between spans, which starts and ends
at its first and last event.

Disabled, ``span`` returns one shared no-op that blocks on nothing and
records nothing, and no listener is registered.  One thread records.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Dict, List, Optional

import jax

OUTSIDE = "outside"

_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_PARTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  _COMPILE)
_CACHE = {"/jax/compilation_cache/cache_hits": "cache_hits",
          "/jax/compilation_cache/cache_misses": "cache_misses"}


@dataclasses.dataclass
class Span:
    """One recorded span (or ``outside`` record) and its counters."""
    name: str
    span_id: int
    parent_id: Optional[int]
    sweep_id: Optional[int]
    start_ns: int
    end_ns: Optional[int] = None
    counters: Dict[str, object] = dataclasses.field(default_factory=dict)

    def add(self, **counters) -> None:
        """Add to numeric counters; set the others (e.g. a name)."""
        for k, v in counters.items():
            if isinstance(v, str):
                self.counters[k] = v
            else:
                self.counters[k] = self.counters.get(k, 0) + v

    def ready(self, x) -> None:
        """Block on the device arrays in pytree ``x``."""
        jax.block_until_ready(x)

    def __enter__(self) -> "Span":
        return _REC.open(self)

    def __exit__(self, *exc) -> bool:
        _REC.close(self)
        return False


class _Noop:
    """The span handed out while the recorder is off."""

    def add(self, **counters) -> None:
        pass

    def ready(self, x) -> None:
        pass

    def __bool__(self) -> bool:
        return False

    def __enter__(self) -> "_Noop":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _Noop()


class _Recorder:
    def __init__(self):
        self.enabled = False
        self.spans: List[Span] = []
        self.stack: List[Span] = []
        self.annotations: Dict[int, object] = {}
        self.outside: Optional[Span] = None
        self.ids = itertools.count(1)

    def open(self, sp: Span) -> Span:
        parent = self.stack[-1] if self.stack else None
        sp.span_id = next(self.ids)
        sp.parent_id = None if parent is None else parent.span_id
        sp.sweep_id = (sp.span_id if sp.name == "sweep"
                       else None if parent is None else parent.sweep_id)
        ann = jax.profiler.TraceAnnotation(sp.name)
        ann.__enter__()
        self.annotations[sp.span_id] = ann
        self.stack.append(sp)
        self.spans.append(sp)
        self.outside = None
        sp.start_ns = time.perf_counter_ns()
        return sp

    def close(self, sp: Span) -> None:
        sp.end_ns = time.perf_counter_ns()
        self.annotations.pop(sp.span_id).__exit__(None, None, None)
        if sp in self.stack:        # a recording started inside it
            self.stack.remove(sp)

    def target(self) -> Span:
        """The innermost open span, else the current ``outside`` record."""
        if self.stack:
            return self.stack[-1]
        now = time.perf_counter_ns()
        if self.outside is None:
            self.outside = Span(OUTSIDE, next(self.ids), None, None, now)
            self.spans.append(self.outside)
        self.outside.end_ns = now
        return self.outside

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_PARTS:
            sp = self.target()
            sp.add(compile_s=duration)
            if event == _COMPILE:
                sp.add(compiles=1)

    def on_event(self, event: str, **_kw) -> None:
        if event in _CACHE:
            self.target().add(**{_CACHE[event]: 1})


_REC = _Recorder()


def span(name: str):
    """A span named ``name``; the shared no-op while the recorder is off.

    Use as ``with obs.span("sweep.build") as sp:``; ``sp.ready(out)``
    blocks on ``out`` and ``if sp: sp.add(...)`` counts, both only
    while the recorder is on.
    """
    if not _REC.enabled:
        return _NOOP
    return Span(name, 0, None, None, 0)


def enable() -> None:
    """Start a fresh recording and listen to JAX's compile events."""
    if _REC.enabled:
        disable()
    _REC.spans, _REC.stack, _REC.outside = [], [], None
    jax.monitoring.register_event_duration_secs_listener(_REC.on_duration)
    jax.monitoring.register_event_listener(_REC.on_event)
    _REC.enabled = True


def disable() -> None:
    """Stop recording and stop listening; the records stay readable."""
    if not _REC.enabled:
        return
    _REC.enabled = False
    jax.monitoring.unregister_event_duration_listener(_REC.on_duration)
    jax.monitoring.unregister_event_listener(_REC.on_event)


def records() -> List[Span]:
    """The finished spans and ``outside`` records, in the order they
    started."""
    return [s for s in _REC.spans if s.end_ns is not None]


def totals() -> Dict[str, float]:
    """Each numeric counter summed over every record."""
    out: Dict[str, float] = {}
    for s in _REC.spans:
        for k, v in s.counters.items():
            if not isinstance(v, str):
                out[k] = out.get(k, 0) + v
    return out

"""Spans and counters the benchmark records around the program's layers.

``LayerSpans`` wraps, for a traced run only, the calls a sweep makes into
each layer of the program:

- ``bench.build``: grid build and trace generation
  (``engine.build_sweep_batch`` / ``engine.build_tiering_batch``);
- ``bench.executor``: one long device program (``engine.run_traces``, the
  static program; ``tiering_dyn.run_dynamic``, the epoch program);
- ``bench.timing``: the timing fixed point (``engine.time_batch``);
- ``bench.sweep``: the whole ``engine.run_sweep``, so that what lies
  outside the three above (route building, row assembly) shows too.

Each call is marked with a ``jax.profiler.TraceAnnotation`` of that name,
blocked on what it returns and recorded on the host clock
(``time.perf_counter_ns``).  An executor call also records its program
interval and the row-steps it ran (batch rows times padded scan steps),
and pauses the profiler, if one is given, while its program runs.

``CompileEvents`` and ``CacheEvents`` count compilations and
persistent-cache hits and misses from ``jax.monitoring`` events.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

#: (module, function, span name, is a long device program)
WRAPPED = (
    ("repro.core.engine", "build_sweep_batch", "bench.build", False),
    ("repro.core.engine", "build_tiering_batch", "bench.build", False),
    ("repro.core.engine", "run_traces", "bench.executor", True),
    ("repro.core.tiering_dyn", "run_dynamic", "bench.executor", True),
    ("repro.core.engine", "time_batch", "bench.timing", False),
    ("repro.core.engine", "run_sweep", "bench.sweep", False),
)


class LayerSpans:
    """Host spans, program intervals and row-steps of the wrapped calls.

    Program labels are ``<module>.<function>`` of the call, e.g.
    ``engine.run_traces``.
    """

    def __init__(self, profiler=None):
        self.profiler = profiler
        self.seconds = collections.Counter()
        self.row_steps = collections.Counter()
        self.spans = []           # (start_ns, end_ns, span name)
        self.programs = []        # (start_ns, end_ns, program label)

    @contextlib.contextmanager
    def watch(self):
        import importlib

        import jax
        originals = []
        for mod_name, fn_name, span, program in WRAPPED:
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, fn_name)
            originals.append((mod, fn_name, fn))
            label = f"{mod_name.rsplit('.', 1)[-1]}.{fn_name}"
            setattr(mod, fn_name,
                    self._wrap(jax, fn, span, label if program else None))
        try:
            yield self
        finally:
            for mod, fn_name, fn in originals:
                setattr(mod, fn_name, fn)

    def _wrap(self, jax, fn, span, program):
        def call(*args, **kw):
            with jax.profiler.TraceAnnotation(span):
                if program and self.profiler is not None:
                    self.profiler.stop()
                t = time.perf_counter_ns()
                out = fn(*args, **kw)
                _ready(out)
                t_end = time.perf_counter_ns()
                if program and self.profiler is not None:
                    self.profiler.start()
            self.spans.append((t, t_end, span))
            self.seconds[span] += (t_end - t) / 1e9
            if program:
                b, n = args[1].shape          # (p, addr, ...): (B, N) trace
                self.row_steps[program] += int(b) * int(n)
                self.programs.append((t, t_end, program))
            return out
        return call


def _ready(x) -> None:
    """Block on every device array in x (dataclasses and tuples too)."""
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        for f in dataclasses.fields(x):
            _ready(getattr(x, f.name))
    elif isinstance(x, (list, tuple)):
        for y in x:
            _ready(y)
    elif hasattr(x, "block_until_ready"):
        x.block_until_ready()


class CompileEvents:
    """Backend compilations (or loads of a cached executable) so far."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1


class CacheEvents:
    """Hits and misses of JAX's persistent compilation cache."""

    def __init__(self):
        import jax
        self.seen = collections.Counter()
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            self.seen[event.rsplit("_", 1)[-1]] += 1

    def __str__(self) -> str:
        return (f"compile cache {self.seen['hits']} hits, "
                f"{self.seen['misses']} misses")

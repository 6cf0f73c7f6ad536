"""The profiler over a traced run's window, in sessions.

``WindowProfiler`` runs JAX's profiler over the window and is stopped
while each long device program runs (``spans.LayerSpans`` calls
``stop`` before dispatching one and ``start`` once it has completed):
traced op by op, one sweep's scan would write millions of events.  Each
session starts with a ``bench.anchor`` annotation whose host-clock time
it records, so that ``reduce`` can put every session on one clock.  The
time spent stopping and starting the profiler is recorded too, and left
out of the window.
"""
from __future__ import annotations

import os
import time
from typing import List, Tuple

from reduce import ANCHOR


class WindowProfiler:
    def __init__(self, root: str):
        self.root = root
        self.sessions: List[Tuple[str, int]] = []     # (dir, anchor_ns)
        self.paused: List[Tuple[int, int]] = []       # profiler's own time
        self.on = False

    def start(self) -> None:
        import jax
        t0 = time.perf_counter_ns()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        path = os.path.join(self.root, f"session{len(self.sessions):03d}")
        jax.profiler.start_trace(path, profiler_options=opts)
        with jax.profiler.TraceAnnotation(ANCHOR):
            anchor = time.perf_counter_ns()
        self.sessions.append((path, anchor))
        self.on = True
        self.paused.append((t0, time.perf_counter_ns()))

    def stop(self) -> None:
        import jax
        if not self.on:
            return
        t0 = time.perf_counter_ns()
        jax.profiler.stop_trace()
        self.on = False
        self.paused.append((t0, time.perf_counter_ns()))

"""From the traced window to the numbers the per-layer metrics read.

A whole sweep cannot be traced op by op: the scan of one v5e sweep emits
about 0.9 million device op events a second, and a trace of one 8 s
sweep took 167 s to write out (305 MB) and still dropped events.  So the
traced run keeps the profiler on for the whole window except while one
of the sweep's long device programs runs (``tracing.WindowProfiler``);
each such program is timed on the host clock from its dispatch to its
completion, and the device counts as busy all that time.

What is read from each profiler session (JAX's ``.xplane.pb``, through
``jax.profiler.ProfileData``):

- device intervals: on each ``/device:...`` plane, the events of the
  ``XLA Ops`` line where the session has one, else of ``XLA Modules``;
- programs: the ``XLA Modules`` events, by name (``jit_<function>``
  without the trailing fingerprint);
- the ``bench.anchor`` annotation, whose host-clock time the benchmark
  recorded: it maps the session's clock onto ``time.perf_counter_ns``.

The window is the benchmark's own (host clock), less the time spent
stopping and starting the profiler.  Busy time is the union of the
device intervals and the untraced programs inside it, averaged over the
chips that ran anything; each idle gap is named by the innermost span
the benchmark recorded around its midpoint, and the breakdown sums the
idle seconds per name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)
ANCHOR = "bench.anchor"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """``jit_foo(1234)`` -> ``jit_foo``."""
    return _FINGERPRINT.sub("", event_name)


@dataclasses.dataclass
class Session:
    """One profiler session, on the host's ``perf_counter_ns`` clock."""
    device: Dict[str, List[Interval]]        # plane -> op (or program) events
    programs: Dict[str, List[Interval]]      # plane -> program executions


def from_profile(pd, anchor_ns: float) -> Session:
    """Device and program intervals of one session, shifted so that its
    ``bench.anchor`` annotation starts at ``anchor_ns``."""
    anchor = None
    device: Dict[str, List[Interval]] = {}
    programs: Dict[str, List[Interval]] = {}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:"):
            mods = ([(e.start_ns, e.end_ns, program_name(e.name))
                     for e in lines["XLA Modules"].events]
                    if "XLA Modules" in lines else [])
            ops = ([(e.start_ns, e.end_ns, e.name)
                    for e in lines["XLA Ops"].events]
                   if "XLA Ops" in lines else [])
            if mods or ops:
                programs[plane.name] = mods
                device[plane.name] = ops or mods
        elif plane.name.startswith("/host:") and anchor is None:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == ANCHOR:
                        anchor = e.start_ns
                        break
    if anchor is None:
        raise ValueError(f"the session holds no {ANCHOR} annotation")
    shift = anchor_ns - anchor

    def moved(ev):
        return [(s + shift, e + shift, n) for s, e, n in ev]
    return Session(device={k: moved(v) for k, v in device.items()},
                   programs={k: moved(v) for k, v in programs.items()})


def load(session_dir: str, anchor_ns: float) -> Session:
    """The session the profiler wrote under ``session_dir``."""
    import jax
    files = glob.glob(os.path.join(session_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {session_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(files[0]),
                        anchor_ns)


def union(intervals: Iterable[Tuple]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) pairs."""
    out: List[List[float]] = []
    for s, e, *_ in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Tuple], lo: float, hi: float) -> List[Tuple]:
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged intervals ``a`` less merged intervals ``b``."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


class Context:
    """What a metric reader gets for the traced window.

    ``sessions``: the profiler sessions; ``programs``: the untraced device
    programs (start, end, label), host clock; ``spans``: the benchmark's
    host spans (start, end, name); ``window``: (start, end); ``paused``:
    the profiler's own stop and start intervals; ``host_seconds``,
    ``row_steps``: totals per span name and per program label.
    """

    def __init__(self, sessions: Sequence[Session],
                 programs: Sequence[Interval], spans: Sequence[Interval],
                 window: Tuple[float, float],
                 paused: Sequence[Tuple[float, float]],
                 host_seconds: Dict[str, float], row_steps: Dict[str, int],
                 sweeps: int, window_compiles: int):
        self.sessions = list(sessions)
        self.spans = list(spans)
        self.programs = list(programs)
        self.host_seconds = host_seconds
        self.row_steps = row_steps
        self.sweeps = sweeps
        self.window_compiles = window_compiles
        self.lo, self.hi = window
        self.excluded = union(clip(paused, self.lo, self.hi))
        self.window_s = (self.hi - self.lo - length(self.excluded)) / 1e9
        planes: Dict[str, List[Interval]] = {}
        for sess in self.sessions:
            for plane, ev in sess.device.items():
                planes.setdefault(plane, []).extend(ev)
        progs = clip(self.programs, self.lo, self.hi)
        busy = {p: subtract(union(clip(ev, self.lo, self.hi) + progs),
                            self.excluded) for p, ev in planes.items()}
        if not busy and progs:
            busy = {"programs": subtract(union(progs), self.excluded)}
        self._busy = busy
        used = [b for b in busy.values() if b]
        self.busy_s = (sum(length(b) for b in used) / max(len(used), 1)
                       / 1e9)

    def program_seconds(self, label: str) -> float:
        """Seconds of the untraced programs with this label."""
        return sum(e - s for s, e, n in self.programs if n == label) / 1e9

    def idle_share(self):
        if self.window_s <= 0 or self.busy_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def _host_at(self, t: float) -> str:
        inner = [h for h in self.spans if h[0] <= t <= h[1]]
        return (min(inner, key=lambda h: h[1] - h[0])[2] if inner
                else "between sweeps")

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Idle gaps of the first busy chip: (host span, seconds)."""
        busy = next((b for b in self._busy.values() if b), [])
        taken = union([(s, e) for s, e in busy] + list(self.excluded))
        gaps = subtract([(self.lo, self.hi)], taken)
        return [(self._host_at((s + e) / 2), (e - s) / 1e9) for s, e in gaps]

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        per: Dict[str, float] = {}
        for sess in self.sessions:
            for ev in sess.programs.values():
                for s, e, n in clip(ev, self.lo, self.hi):
                    per[n] = per.get(n, 0.0) + (e - s) / 1e9
        for s, e, n in clip(self.programs, self.lo, self.hi):
            per[n] = per.get(n, 0.0) + (e - s) / 1e9
        idle: Dict[str, float] = {}
        for name, seconds in self.idle_gaps():
            idle[name] = idle.get(name, 0.0) + seconds
        return {"device_ops": [list(kv) for kv in _top(per, top)],
                "idle_gaps": [list(kv) for kv in _top(idle, top)]}


def _top(totals: Dict[str, float], n: int) -> List[Tuple[str, float]]:
    return sorted(totals.items(), key=lambda kv: -kv[1])[:n]

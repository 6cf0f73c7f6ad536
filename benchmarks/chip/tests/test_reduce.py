"""The trace reduction, on a small recorded session and on intervals
whose answer is known.

``data/session.xplane.pb`` is one profiler session recorded on a TPU v5e
by ``record_trace.py``: three small device programs, each after a 10 ms
``bench.build`` span.  The busy time and gaps the reduction reports are
checked against a count made here, microsecond by microsecond.
"""
import collections
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import reduce  # noqa: E402

DATA = HERE / "data"


def test_union_and_subtract():
    assert reduce.union([(5, 7, "a"), (0, 2, "b"), (1, 3, "c"),
                         (9, 9, "d")]) == [(0, 3), (5, 7)]
    assert reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert reduce.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert reduce.clip([(0, 10, "x")], 2, 5) == [(2, 5, "x")]


def _context(sessions, programs, spans, window, paused):
    return reduce.Context(sessions, programs, spans, window, paused,
                          host_seconds={}, row_steps={}, sweeps=1,
                          window_compiles=0)


def test_context_on_known_intervals():
    ms = 1_000_000
    sess = reduce.Session(
        device={"/device:TPU:0": [(10 * ms, 20 * ms, "op"),
                                  (15 * ms, 30 * ms, "op")]},
        programs={"/device:TPU:0": [(10 * ms, 30 * ms, "jit_f")]})
    programs = [(50 * ms, 90 * ms, "engine.run_traces")]
    spans = [(0, 100 * ms, "bench.sweep"), (30 * ms, 45 * ms, "bench.build")]
    ctx = _context([sess], programs, spans, (0, 100 * ms),
                   [(45 * ms, 50 * ms)])
    # window 100 ms less 5 ms paused; busy 20 + 40 ms
    assert ctx.window_s == pytest.approx(0.095)
    assert ctx.busy_s == pytest.approx(0.060)
    assert ctx.idle_share() == pytest.approx(1 - 60 / 95)
    assert ctx.program_seconds("engine.run_traces") == pytest.approx(0.040)
    gaps = sorted(ctx.idle_gaps(), key=lambda g: -g[1])
    assert gaps == [("bench.build", pytest.approx(0.015)),
                    ("bench.sweep", pytest.approx(0.010)),
                    ("bench.sweep", pytest.approx(0.010))]
    ops = dict(ctx.breakdown()["device_ops"])
    assert ops == {"engine.run_traces": pytest.approx(0.040),
                   "jit_f": pytest.approx(0.020)}


@pytest.fixture(scope="module")
def recorded():
    if not (DATA / "session.xplane.pb").exists():
        pytest.skip("no recorded session; run record_trace.py on a TPU")
    import jax
    meta = json.loads((DATA / "session.json").read_text())
    pd = jax.profiler.ProfileData.from_serialized_xspace(
        (DATA / "session.xplane.pb").read_bytes())
    return pd, meta


def test_recorded_session(recorded):
    pd, meta = recorded
    sess = reduce.from_profile(pd, meta["anchor_ns"])
    (plane, ops), = sess.device.items()
    assert plane.startswith("/device:TPU")
    mods = sess.programs[plane]
    # three executions of the one program, each after its build span
    names = collections.Counter(m[2] for m in mods)
    (prog,) = [n for n, c in names.items() if c == 3]
    f_runs = [m for m in mods if m[2] == prog]
    # the profiler puts this chip's events about 1 ms before the host's
    # (the program is dispatched after its build span ends)
    for (s, e, _), (b0, b1, _) in zip(f_runs, meta["spans"]):
        assert b1 - 2_000_000 <= s < e
    lo, hi = meta["window"]
    ctx = _context([sess], [], meta["spans"], (lo, hi), meta["paused"])
    # busy time, counted here at 1 us resolution from the raw op events
    step = 1000
    marks = set()
    for s, e, _ in ops:
        for t in range(int(max(s, lo)) // step, int(min(e, hi)) // step):
            marks.add(t)
    assert ctx.busy_s == pytest.approx(len(marks) * step / 1e9, rel=0.02,
                                       abs=2e-5)
    gaps = ctx.idle_gaps()
    assert sum(g for _, g in gaps) == pytest.approx(
        ctx.window_s - ctx.busy_s, rel=1e-9)
    assert [n for n, g in gaps if g > 0.009].count("bench.build") == 3

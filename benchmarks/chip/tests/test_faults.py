"""A run whose timed path is broken must come out as not correct.

Each test drives ``run_cell.run`` past its look for a chip, at a small
cache geometry on the CPU, with one fault planted in the program under
it, and checks ``correct``; a run with no fault must be correct.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

The faults a cell of this benchmark can have:

- the MESI step returns its state unchanged;
- half of the batch is left out: half the rows (or, in a one-row batch,
  the second half of the trace) never reach the cache model;
- an answer is altered where it is produced: one counter, or the time
  of one row, as the timing fixed point returns it.

Every cell runs on one chip, so there is no exchange between chips to
leave out.
"""
import dataclasses
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import run_cell  # noqa: E402

CELLS = ("direct1-chase-static", "switch4-kv-tiering",
         "direct1-hotcold-tiering")


def small(loaded):
    """The cell at a cache the CPU simulates in well under a second."""
    loaded["config"]["cache"].update(l1_bytes=8192, l1_ways=2,
                                     l2_bytes=16384, l2_ways=8)


def _run(cell):
    import jax
    jax.clear_caches()          # a planted fault must be traced anew
    return run_cell.run(cell, 2 ** 31 + 11, 0.2, False, require_tpu=False,
                        adjust=small)


def _step_unchanged(monkeypatch):
    from repro.core import cache
    monkeypatch.setattr(cache, "_packed_step",
                        lambda p, carry, x: (carry, None))


def _half_batch(monkeypatch):
    from repro.core import engine
    stack = engine.stack_device_traces

    def dropped(traces, pad_to_multiple=1):
        tb = stack(traces, pad_to_multiple)
        b = tb.addr.shape[0]
        half = int(tb.n_valid[0]) // 2
        addr = (tb.addr.at[b // 2:].set(engine.SENTINEL) if b > 1
                else tb.addr.at[:, half:].set(engine.SENTINEL))
        return dataclasses.replace(tb, addr=addr)
    monkeypatch.setattr(engine, "stack_device_traces", dropped)


def _counter_altered(monkeypatch):
    from repro.core import engine
    time_batch = engine.time_batch

    def altered(timing, cpus, stats, *args, **kw):
        stats = stats.copy()
        stats[0, 2] += 1                      # one more L2 hit
        return time_batch(timing, cpus, stats, *args, **kw)
    monkeypatch.setattr(engine, "time_batch", altered)


def _time_altered(monkeypatch):
    from repro.core import engine
    time_batch = engine.time_batch

    def altered(*args, **kw):
        out = time_batch(*args, **kw)
        out[0].time_ns *= 1 + 1e-8
        return out
    monkeypatch.setattr(engine, "time_batch", altered)


FAULTS = {"step_unchanged": _step_unchanged, "half_batch": _half_batch,
          "counter_altered": _counter_altered,
          "time_altered": _time_altered}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run(cell)
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"]

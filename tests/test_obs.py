"""The span and counter recorder (`repro.core.obs`) on a tiny CPU sweep.

Both sweep programs are covered: a grid with no tiering axis runs the
static program, one with a dynamic tiering entry the epoch program.
The recorder must nest the six spans under their sweep, count the batch
from its shapes, change no simulated number, and, while off, record
nothing, open no span and listen to nothing.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache as C
from repro.core import engine, numa, obs
from repro.core import route as route_mod
from repro.core import tiering_dyn as td
from repro.core.machine import CPUModel
from repro.core.timing import TimingConfig
from repro.workloads import HotCold, PointerChase

CACHE = C.CacheParams(l1_bytes=8 * 1024, l1_ways=2,
                      l2_bytes=16 * 1024, l2_ways=8)
TIMING = TimingConfig()
DYN = td.DynamicTiering(epoch_len=128, budget=2)
KINDS = ("static", "dynamic")
PARENT = {"sweep.build": "sweep", "sweep.build.trace": "sweep.build",
          "sweep.prep": "sweep", "sweep.program": "sweep",
          "sweep.timing": "sweep"}
WORKLOADS = (PointerChase(seed=3), HotCold(seed=4))
FOOTPRINTS = (1, 2)


def _spec(kind):
    spec = engine.SweepSpec(
        footprint_factors=FOOTPRINTS,
        policies=(numa.ZNuma(1.0), numa.WeightedInterleave(1, 1)),
        cpus=(CPUModel(kind="o3", mlp=8),),
        topologies=(route_mod.direct(1),), workloads=WORKLOADS)
    if kind == "dynamic":
        spec = dataclasses.replace(spec, tiering=(None, DYN))
    return spec


def _recorded(fn):
    obs.enable()
    try:
        out = fn()
    finally:
        obs.disable()
    return out, obs.records()


@pytest.fixture(scope="module", params=KINDS)
def traced(request):
    """(kind, rows of the first sweep, records of two sweeps)."""
    spec = _spec(request.param)

    def two_sweeps():
        rows = engine.run_sweep(spec, CACHE, TIMING)
        engine.run_sweep(spec, CACHE, TIMING)
        return rows
    rows, recs = _recorded(two_sweeps)
    return request.param, rows, recs


def _named(recs, name):
    return [r for r in recs if r.name == name]


def test_spans_nest_under_their_sweep(traced):
    _, _, recs = traced
    spans = [r for r in recs if r.name != obs.OUTSIDE]
    assert {r.name for r in spans} == set(PARENT) | {"sweep"}
    by_id = {r.span_id: r for r in spans}
    sweeps = _named(spans, "sweep")
    assert len(sweeps) == 2
    for sw in sweeps:
        assert sw.parent_id is None and sw.sweep_id == sw.span_id
        assert sw.counters["rows"] == 8 * (1 if traced[0] == "static" else 2)
    for r in spans:
        if r.name == "sweep":
            continue
        parent = by_id[r.parent_id]
        assert parent.name == PARENT[r.name]
        assert r.sweep_id == parent.sweep_id
        assert parent.start_ns <= r.start_ns <= r.end_ns <= parent.end_ns
    # each sweep holds its own build, prep, program and timing
    for sw in sweeps:
        mine = [r for r in spans if r.sweep_id == sw.span_id]
        assert all(sw.start_ns <= r.start_ns and r.end_ns <= sw.end_ns
                   for r in mine)
        for name in ("sweep.build", "sweep.prep", "sweep.program"):
            assert len(_named(mine, name)) == 1, name


def test_counters_are_the_batch_shapes(traced):
    kind, _, recs = traced
    lengths = {(wl.name, k): int(wl.host_trace(k * CACHE.l2_bytes)
                                 .addr.shape[0])
               for wl in WORKLOADS for k in FOOTPRINTS}
    sweep_id = _named(recs, "sweep")[0].span_id
    mine = [r for r in recs if r.sweep_id == sweep_id]
    gen = _named(mine, "sweep.build.trace")
    assert sorted((r.counters["workload"], r.counters["accesses"])
                  for r in gen) == sorted((wl, n) for (wl, _k), n
                                          in lengths.items())
    # every (workload, footprint) is a row per policy, and per tiering
    # entry in the epoch program
    copies = 2 * (1 if kind == "static" else 2)
    pad = 512 if kind == "static" else td.slot_length([DYN])
    (build,) = _named(mine, "sweep.build")
    (program,) = _named(mine, "sweep.program")
    b, n = copies * len(lengths), -(-max(lengths.values()) // pad) * pad
    assert build.counters["rows"] == b and build.counters["steps"] == n
    assert build.counters["accesses"] == copies * sum(lengths.values())
    assert program.counters["row_steps"] == b * n
    assert program.counters["segments"] == 1
    assert program.counters["program"] == (
        "static" if kind == "static" else "epoch")
    # the CPU resolves both programs to the reference scan
    assert program.counters["backend"] == "reference"
    (sweep,) = _named(mine, "sweep")
    assert sum(r.counters["rows"] for r in _named(mine, "sweep.timing")) \
        == sweep.counters["rows"]


def test_rows_bitwise_equal_with_the_recorder_on_and_off(traced):
    kind, rows_on, _ = traced
    rows_off = engine.run_sweep(_spec(kind), CACHE, TIMING)
    assert [r["stats"] for r in rows_on] == [r["stats"] for r in rows_off]
    assert repr(rows_on) == repr(rows_off)     # timing columns, exactly


@pytest.mark.parametrize("kind", KINDS)
def test_segmented_programs_count_padding_and_segments(kind):
    rng = np.random.default_rng(5)
    b, n, seg = 3, 700, 256
    addr = jnp.asarray(rng.integers(0, 512, (b, n)), jnp.int32)
    wr = jnp.asarray(rng.integers(0, 2, (b, n)), jnp.int32)
    z = jnp.zeros((b, n), jnp.int32)
    if kind == "static":
        def run():
            return engine.run_traces(CACHE, addr, wr, z, z, segment=seg)
        want_steps, want_segments = b * 768, 3
    else:
        slot = 100
        one = jnp.ones((b,), jnp.int32)

        def run():
            return td.run_dynamic(
                CACHE, addr, wr, z, z + 1, slot_len=slot, k_max=2,
                dyn_flag=one, page_map0=jnp.ones((b, 4), jnp.int32),
                n_pages=4 * one, budget=2 * one, threshold=one,
                period=2 * one, dram_cap=4 * one,
                page_target_lines=jnp.zeros((b, 4, 2), jnp.int32)
                .at[:, :, 1].set(64), segment_slots=3)
        want_steps, want_segments = b * n, 3
    _, recs = _recorded(run)
    names = [r.name for r in recs if r.name != obs.OUTSIDE]
    assert names == ["sweep.prep", "sweep.program"]
    (program,) = _named(recs, "sweep.program")
    assert program.sweep_id is None and program.parent_id is None
    assert program.counters["row_steps"] == want_steps
    assert program.counters["segments"] == want_segments


@pytest.mark.parametrize("segment", [None, 256])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_program_span_names_the_backend_that_ran(backend, segment):
    rng = np.random.default_rng(6)
    addr = jnp.asarray(rng.integers(0, 512, (2, 300)), jnp.int32)
    _, recs = _recorded(lambda: engine.run_traces(
        CACHE, addr, None, backend=backend, chunk=128, segment=segment))
    (program,) = _named(recs, "sweep.program")
    assert program.counters["backend"] == backend


@pytest.mark.parametrize("segment", [None, 256])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_program_span_counts_the_state_and_the_kernels_vmem(backend,
                                                            segment):
    from repro.kernels import cache_sim
    rng = np.random.default_rng(7)
    addr = jnp.asarray(rng.integers(0, 512, (2, 300)), jnp.int32)
    _, recs = _recorded(lambda: engine.run_traces(
        CACHE, addr, None, backend=backend, chunk=128, segment=segment))
    (program,) = _named(recs, "sweep.program")
    # 8 KiB 2-way L1 and 16 KiB 8-way L2 of 64 B lines: 128 and 256
    # lines, 3 and 5 int32 fields a line
    assert program.counters["state_bytes"] == 4 * (3 * 128 + 5 * 256)
    assert program.counters["vmem_limit_bytes"] == (
        cache_sim.vmem_bytes(CACHE) + cache_sim.VMEM_MARGIN
        if backend == "pallas" else 0)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_epoch_program_span_counts_the_kernels_vmem(backend):
    from repro.kernels import cache_sim
    rng = np.random.default_rng(8)
    b, slot, pages = 2, 128, 6
    addr = jnp.asarray(rng.integers(0, pages * 64, (b, 2 * slot)),
                       jnp.int32)
    z = jnp.zeros(addr.shape, jnp.int32)
    one = jnp.ones((b,), jnp.int32)
    _, recs = _recorded(lambda: td.run_dynamic(
        CACHE, addr, z, z, z + 1, slot_len=slot, k_max=2, dyn_flag=one,
        page_map0=jnp.ones((b, pages), jnp.int32), n_pages=pages * one,
        budget=2 * one, threshold=one, period=one, dram_cap=pages * one,
        page_target_lines=jnp.zeros((b, pages, 2), jnp.int32)
        .at[:, :, 1].set(64), backend=backend))
    (program,) = _named(recs, "sweep.program")
    assert program.counters["program"] == "epoch"
    assert program.counters["backend"] == backend
    assert program.counters["vmem_limit_bytes"] == (
        cache_sim.vmem_limit_bytes(CACHE, n_pages=pages)
        if backend == "pallas" else 0)


def test_off_records_nothing_opens_nothing_and_listens_to_nothing(
        monkeypatch):
    from jax._src import monitoring
    obs.disable()
    assert obs.span("sweep") is obs.span("sweep.build")
    assert not obs.span("sweep")
    listeners = (monitoring.get_event_duration_listeners(),
                 monitoring.get_event_listeners())
    assert all(obs._REC.on_duration != f and obs._REC.on_event != f
               for group in listeners for f in group)

    def refused(*a, **kw):
        raise AssertionError("the recorder acted while off")
    monkeypatch.setattr(obs._REC, "open", refused)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refused)
    monkeypatch.setattr(obs.Span, "ready", refused)
    before = obs.records()
    engine.run_sweep(_spec("dynamic"), CACHE, TIMING)
    assert obs.records() == before
    assert (monitoring.get_event_duration_listeners(),
            monitoring.get_event_listeners()) == listeners


def test_compile_and_cache_events_go_to_the_innermost_span():
    x = jnp.arange(7, dtype=jnp.int32)
    f = jax.jit(lambda v: v * 3 + 1)
    g = jax.jit(lambda v: v - 2)

    def run():
        with obs.span("sweep"):
            with obs.span("sweep.program") as sp:
                sp.ready(f(x))
                jax.monitoring.record_event(
                    "/jax/compilation_cache/cache_hits")
        g(x).block_until_ready()                # no span open
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    _, recs = _recorded(run)
    (outer,) = _named(recs, "sweep")
    (inner,) = _named(recs, "sweep.program")
    assert inner.counters["compiles"] == 1
    assert inner.counters["compile_s"] > 0
    assert inner.counters["cache_hits"] == 1
    assert "compiles" not in outer.counters
    (outside,) = _named(recs, obs.OUTSIDE)
    assert outside.counters["compiles"] == 1
    assert outside.counters["cache_misses"] == 1
    assert outer.end_ns <= outside.start_ns <= outside.end_ns
    assert obs.totals()["compiles"] == 2

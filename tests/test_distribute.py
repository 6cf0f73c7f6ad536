"""Sharded + streaming sweep executor: strategy changes, results don't.

The contract under test (ISSUE acceptance): any `Mesh`/`stream_chunk`
choice is an *execution strategy* — sharded-vs-single-program stat
parity is **bitwise** (dynamic-tiering rows included), ragged grids are
padding-invariant, and streaming a trace through the scan carry equals
the resident scan entry-for-entry (stats and final cache state).  The
`mesh=None`/`stream_chunk=None` path must be exactly the legacy engine
path (the golden fixtures additionally pin the sharded+streamed rows —
see tests/test_golden_stats.py).
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import cache as C
from repro.core import distribute, engine, numa
from repro.core import route as route_mod
from repro.core.machine import CPUModel
from repro.core.tiering_dyn import DynamicTiering
from repro.core.timing import TimingConfig

RNG = np.random.default_rng(11)

CACHE = C.CacheParams(l1_bytes=8 * 1024, l1_ways=2,
                      l2_bytes=16 * 1024, l2_ways=8)
TIMING = TimingConfig()
CPUS = (CPUModel(kind="o3", mlp=8),)


def grid_spec(**kw):
    """A 8-row grid (2 footprints x 2 policies x 2 topologies)."""
    base = dict(footprint_factors=(1, 2),
                policies=(numa.ZNuma(1.0), numa.WeightedInterleave(1, 1)),
                cpus=CPUS,
                topologies=(route_mod.direct(1), route_mod.direct(2)))
    base.update(kw)
    return engine.SweepSpec(**base)


def rand_batch(b, n, addr_hi=256):
    return (RNG.integers(0, addr_hi, (b, n)).astype(np.int32),
            RNG.integers(0, 2, (b, n)).astype(np.int32),
            RNG.integers(0, 2, (b, n)).astype(np.int32))


# ---------------------------------------------------------------------------
# mesh=None / stream_chunk=None: exactly the legacy path
# ---------------------------------------------------------------------------
def test_defaults_are_the_legacy_path():
    spec = grid_spec()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    rows = distribute.run_sweep(spec, CACHE, TIMING,
                                mesh=None, stream_chunk=None)
    assert rows == legacy            # dict equality: floats to the bit


# ---------------------------------------------------------------------------
# sharded-vs-single-program bitwise parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mesh", [1, 2, 3, distribute.Mesh(n_shards=5)])
def test_sharded_rows_bitwise_equal(mesh):
    spec = grid_spec()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    rows = distribute.run_sweep(spec, CACHE, TIMING, mesh=mesh)
    assert rows == legacy


def test_ragged_grid_padding_invariance():
    # 6 batch rows (2 footprints x 3 policies) over shard counts that do
    # and do not divide it: padding rows must never perturb real rows
    spec = grid_spec(policies=(numa.ZNuma(1.0), numa.ZNuma(0.0),
                               numa.WeightedInterleave(1, 1)),
                     topologies=())
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    for shards in (2, 3, 4, 5, 6):
        rows = distribute.run_sweep(spec, CACHE, TIMING, mesh=shards)
        assert rows == legacy, f"shards={shards}"


def test_sharded_tiering_rows_bitwise_equal():
    spec = grid_spec(
        footprint_factors=(2,), policies=(numa.ZNuma(1.0),),
        topologies=(route_mod.direct(2),),
        tiering=(None, DynamicTiering(epoch_len=512, budget=4,
                                      threshold=2)))
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    for mesh, chunk in ((2, None), (3, None), (None, 512), (2, 1024)):
        rows = distribute.run_sweep(spec, CACHE, TIMING, mesh=mesh,
                                    stream_chunk=chunk)
        assert rows == legacy, f"mesh={mesh} stream_chunk={chunk}"


def test_pallas_backend_shards_via_fallback():
    spec = grid_spec(topologies=(), footprint_factors=(1,),
                     backend="pallas")
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    rows = distribute.run_sweep(spec, CACHE, TIMING, mesh=2)
    assert [r["stats"] for r in rows] == [r["stats"] for r in legacy]
    # stream_chunk now routes through the kernel's segment carry —
    # bitwise-equal to the resident run, not a NotImplementedError
    streamed = distribute.run_sweep(spec, CACHE, TIMING, stream_chunk=256)
    assert [r["stats"] for r in streamed] == [r["stats"] for r in legacy]


# ---------------------------------------------------------------------------
# streaming-vs-resident bitwise equality
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,segment", [(250, 64), (256, 256), (100, 512)])
def test_run_traces_segmented_bitwise(n, segment):
    p = C.CacheParams(l1_bytes=4 * 2 * 64, l1_ways=2,
                      l2_bytes=16 * 4 * 64, l2_ways=4)
    addr, wr, tier = rand_batch(3, n)
    s0, st0 = engine.run_traces(p, addr, wr, None, tier)
    s1, st1 = engine.run_traces(p, addr, wr, None, tier, segment=segment)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    for f in st0._fields:
        np.testing.assert_array_equal(np.asarray(getattr(st0, f)),
                                      np.asarray(getattr(st1, f)),
                                      err_msg=f)


def test_stream_traces_source_equals_resident():
    p = C.CacheParams(l1_bytes=4 * 2 * 64, l1_ways=2,
                      l2_bytes=16 * 4 * 64, l2_ways=4)
    addr, wr, tier = rand_batch(2, 333)
    s0, st0 = engine.run_traces(p, addr, wr, None, tier)
    src = distribute.segment_batch((addr, wr, None, tier), 128)
    s1, st1 = distribute.stream_traces(p, src)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    for f in st0._fields:
        np.testing.assert_array_equal(np.asarray(getattr(st0, f)),
                                      np.asarray(getattr(st1, f)),
                                      err_msg=f)


def test_stream_traces_generated_source_bounded_memory():
    # a lazily *generated* source: E repetitions of a base segment whose
    # concatenation is never materialized — the beyond-memory pattern
    p = C.CacheParams(l1_bytes=4 * 2 * 64, l1_ways=2,
                      l2_bytes=16 * 4 * 64, l2_ways=4)
    base = rand_batch(2, 128)
    reps = 6

    def source():
        for _ in range(reps):
            yield (base[0], base[1], None, base[2])

    s0, _ = engine.run_traces(p, np.tile(base[0], (1, reps)),
                              np.tile(base[1], (1, reps)), None,
                              np.tile(base[2], (1, reps)))
    s1, _ = distribute.stream_traces(p, source())
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    # the streamed working set is one segment, not the whole trace
    assert distribute.trace_working_set_bytes(2, 128) * reps \
        == distribute.trace_working_set_bytes(2, 128 * reps)


def test_stream_chunk_sweep_parity():
    spec = grid_spec()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    for chunk in (300, 512, 4096):
        rows = distribute.run_sweep(spec, CACHE, TIMING,
                                    stream_chunk=chunk)
        assert rows == legacy, f"stream_chunk={chunk}"


# ---------------------------------------------------------------------------
# plan arithmetic + validation
# ---------------------------------------------------------------------------
def test_shard_plan_arithmetic():
    assert distribute.shard_plan(8, 2) == (4, 8)
    assert distribute.shard_plan(5, 2) == (3, 6)
    assert distribute.shard_plan(5, 4) == (2, 8)
    assert distribute.shard_plan(1, 1) == (1, 1)
    with pytest.raises(ValueError):
        distribute.shard_plan(0, 2)


def test_explicit_mesh_devices_placement():
    import jax
    mesh = distribute.Mesh(n_shards=2,
                           devices=tuple(jax.local_devices()))
    spec = grid_spec(topologies=())
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    assert distribute.run_sweep(spec, CACHE, TIMING, mesh=mesh) == legacy


def test_mesh_validation_and_shard_count():
    with pytest.raises(ValueError):
        distribute.Mesh(n_shards=-1)
    with pytest.raises(TypeError):
        distribute.run_sweep(grid_spec(), CACHE, TIMING, mesh="four")
    # never more shards than rows (padding can't outnumber the grid)
    assert distribute.Mesh(n_shards=16).shard_count(3) == 3
    assert distribute.Mesh(n_shards=0).shard_count(100) >= 1


def test_streaming_validation():
    with pytest.raises(ValueError):
        distribute.ShardedExecutor(stream_chunk=0)
    with pytest.raises(ValueError):
        distribute.stream_traces(CACHE, iter(()))
    with pytest.raises(ValueError):
        engine.run_traces(CACHE, np.zeros((1, 8), np.int32), None,
                          segment=0)


_FOUR_DEVICE_SCRIPT = """
import dataclasses
import sys
from collections import Counter

import jax
import numpy as np
from repro.core import distribute, engine, numa, tiering_dyn
from repro.core import cache as C
from repro.core import route as route_mod
from repro.core.tiering_dyn import DynamicTiering
from repro.core.timing import TimingConfig

assert len(jax.devices()) == 4
cache = C.CacheParams(l1_bytes=8 * 1024, l1_ways=2, l2_bytes=16 * 1024,
                      l2_ways=8)
program, backend = sys.argv[1], sys.argv[2]
# four static rows (the pmap path, on either backend), or eight
# epoch-program rows (the round-robin path): two real rows a device
spec = engine.SweepSpec(
    footprint_factors=(1, 2), policies=(numa.ZNuma(1.0),),
    topologies=(route_mod.direct(1), route_mod.direct(2)),
    tiering=(() if program == "static"
             else (None, DynamicTiering(epoch_len=512))))
sharded_spec = dataclasses.replace(spec, backend=backend)
live = Counter()       # device id -> rows with non-zero counters

def record(stats):
    for shard in stats.addressable_shards:
        data = np.asarray(shard.data).reshape(-1, stats.shape[-1])
        live[shard.device.id] += int((data != 0).any(-1).sum())

mod, name, stats_of = (
    (distribute, "_pmap_segment", lambda out: out[2])
    if program == "static"
    else (tiering_dyn, "run_dynamic", lambda out: out.stats))
fn = getattr(mod, name)

def spy(*args, **kw):
    out = fn(*args, **kw)
    record(stats_of(out))
    return out

setattr(mod, name, spy)
sharded = distribute.run_sweep(sharded_spec, cache, TimingConfig(),
                               mesh=4)
setattr(mod, name, fn)
rows = 4 if program == "static" else 8
assert dict(live) == {d: rows // 4 for d in range(4)}, dict(live)
assert sharded == engine.run_sweep(spec, cache, TimingConfig())
"""


@pytest.mark.parametrize("program,backend", [
    ("static", "reference"), ("static", "pallas"), ("dynamic", "reference"),
])
def test_four_devices_each_get_a_shard_with_parity(program, backend):
    """Static rows pmap over every device, on the reference scan or the
    Pallas segment kernel, epoch-program shards land on every device
    round-robin and gather back on the host (a concatenate across
    devices is refused); each device simulates real rows and the rows
    equal the one-device sweep on the reference scan.  Needs its own
    process for four virtual CPU devices."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(src),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    res = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_SCRIPT,
                          program, backend],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]

"""Reference-vs-Pallas bitwise parity across the whole sweep matrix.

The contract under test (ISSUE 9 acceptance): ``backend="pallas"`` is a
first-class engine backend — every sweep axis {static, dynamic tiering,
sampled, streamed, sharded, kill-and-resume} produces **bitwise-equal**
counters to the reference vmapped-scan path, on small traces in
interpret mode (the CPU parity oracle for the TPU kernels).  The two
backends expose the *same* carry, so segments may alternate backends
freely and a checkpoint written by one resumes on the other.
"""
import importlib.util
import json
import pathlib

import numpy as np
import pytest
from jax.tree_util import tree_map as jax_tree_map

from repro.core import cache as C
from repro.core import distribute, engine, numa
from repro.core import route as route_mod
from repro.core import tiering_dyn
from repro.core.machine import CPUModel
from repro.core.resilience import (Fault, FaultPlan, RunKilled, RunReport)
from repro.core.sampling import SamplingSpec
from repro.core.tiering_dyn import DynamicTiering
from repro.core.timing import LatencyDistribution, TimingConfig

RNG = np.random.default_rng(9)

# tiny geometry: interpret-mode pallas unrolls the grid at trace time,
# so parity runs must keep sets x ways small
CACHE = C.CacheParams(l1_bytes=2048, l1_ways=2,
                      l2_bytes=8192, l2_ways=4, cores=2)
TIMING = TimingConfig()
CPUS = (CPUModel(kind="o3", mlp=8),)


def rand_trace(b, n, addr_hi=4096, sentinel_tail=0):
    addr = RNG.integers(0, addr_hi, (b, n)).astype(np.int32)
    if sentinel_tail:
        addr[-1, n - sentinel_tail:] = engine.SENTINEL
    wr = RNG.integers(0, 2, (b, n)).astype(np.int32)
    core = RNG.integers(0, CACHE.cores, (b, n)).astype(np.int32)
    tier = RNG.integers(0, CACHE.n_targets, (b, n)).astype(np.int32)
    return addr, wr, core, tier


def assert_run_equal(got, want):
    s0, st0 = want
    s1, st1 = got
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))
    for f in st0._fields:
        np.testing.assert_array_equal(np.asarray(getattr(st1, f)),
                                      np.asarray(getattr(st0, f)),
                                      err_msg=f)


def spec(backend="reference", **kw):
    base = dict(footprint_factors=(2,), policies=(numa.ZNuma(1.0),),
                cpus=CPUS, topologies=(route_mod.direct(2),),
                backend=backend)
    base.update(kw)
    return engine.SweepSpec(**base)


# ---------------------------------------------------------------------------
# which implementation runs where
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("platform,backend,epoch,want", [
    ("cpu", None, False, "reference"),
    ("cpu", None, True, "reference"),
    ("tpu", None, False, "pallas"),     # the static program's kernel
    ("tpu", None, True, "pallas"),      # the epoch program's kernel
    ("tpu", "reference", False, "reference"),
    ("cpu", "pallas", True, "pallas"),
])
def test_backend_resolves_by_platform_and_program(monkeypatch, platform,
                                                  backend, epoch, want):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "platform", lambda: platform)
    assert engine.resolve_backend(backend, epoch=epoch) == want


@pytest.mark.parametrize("cores,l1_kib,l2_mib,want", [
    (4, 64, 2, "pallas"),        # Table I: 0.68 MiB of kernel blocks
    (8, 32, 32, "pallas"),       # a Genoa CCD's 32 MiB L3: 10.05 MiB
    (4, 64, 256, "pallas"),      # 80.05 MiB, the largest 16-way L2 that fits
    (4, 64, 512, "reference"),   # 160.05 MiB, over a v5e's 128 MiB of VMEM
])
def test_default_backend_keeps_the_scan_where_the_state_outgrows_vmem(
        monkeypatch, cores, l1_kib, l2_mib, want):
    from repro.kernels import cache_sim, ops
    monkeypatch.setattr(ops, "platform", lambda: "tpu")
    monkeypatch.setattr(cache_sim, "chip_vmem_bytes",
                        lambda device_kind=None:
                        cache_sim.VMEM_BYTES["TPU v5 lite"])
    p = C.CacheParams(cores=cores, l1_bytes=l1_kib * 1024,
                      l2_bytes=l2_mib * 2 ** 20)
    assert engine.resolve_backend(None, p) == want
    assert engine.resolve_backend("pallas", p) == "pallas"


@pytest.mark.parametrize("l2_mib,n_pages,want", [
    (2, 2048, "pallas"),         # the hotcold cell: 0.75 MiB in all
    (256, 4098, "pallas"),       # 80.05 MiB of cache planes, 0.15 of pages
    (256, 2 ** 21, "reference"), # and 56 MiB of page planes: too many
    (512, 2048, "reference"),    # 160.05 MiB of cache planes alone
])
def test_default_epoch_backend_keeps_the_scan_where_its_state_outgrows_vmem(
        monkeypatch, l2_mib, n_pages, want):
    """The epoch kernel holds the cache planes and, per page, its map,
    counters and lines per target: both count against the chip's VMEM."""
    from repro.kernels import cache_sim, ops
    monkeypatch.setattr(ops, "platform", lambda: "tpu")
    monkeypatch.setattr(cache_sim, "chip_vmem_bytes",
                        lambda device_kind=None:
                        cache_sim.VMEM_BYTES["TPU v5 lite"])
    p = C.CacheParams(cores=4, n_targets=5, l2_bytes=l2_mib * 2 ** 20)
    assert engine.resolve_backend(None, p, epoch=True,
                                  n_pages=n_pages) == want
    assert engine.resolve_backend("pallas", p, epoch=True,
                                  n_pages=n_pages) == "pallas"


def test_unknown_backend_is_refused():
    with pytest.raises(ValueError, match="unknown backend"):
        engine.resolve_backend("mosaic")
    with pytest.raises(ValueError, match="unknown backend"):
        engine.run_sweep(spec("mosaic"), CACHE, TIMING)


def test_default_device_sets_the_platform():
    import jax

    from repro.kernels import ops
    with jax.default_device(jax.devices("cpu")[0]):
        assert ops.platform() == "cpu"
    with jax.default_device("cpu"):
        assert ops.platform() == "cpu"


# ---------------------------------------------------------------------------
# static flat scan
# ---------------------------------------------------------------------------
def test_static_parity():
    args = rand_trace(3, 300, sentinel_tail=40)
    ref = engine.run_traces(CACHE, *args)
    pal = engine.run_traces(CACHE, *args, backend="pallas", chunk=64)
    assert_run_equal(pal, ref)


# ---------------------------------------------------------------------------
# a server host: 8 cores and a many-set 16-way shared level
# ---------------------------------------------------------------------------
BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _bench_module(name):
    spec = importlib.util.spec_from_file_location(f"bench_{name}",
                                                  BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_server_llc_parity_with_the_scan_and_the_plain_reference():
    """The benchmark's Genoa CCD (8 cores, 8-way L1s, 16-way shared L3)
    under its hot/cold traffic and 1:1 interleave, with the caches cut
    to 4 KiB and 64 KiB (64 sets): the kernel's rows equal the scan's,
    bitwise, and pass the plain reference's comparison."""
    grid, reference, compare = (_bench_module(n) for n in
                                ("grid", "reference", "compare"))
    cfg = json.loads((BENCH / "configs" / "genoa-ccd-direct1.json")
                     .read_text())
    cfg["cache"].update(l1_bytes=4096, l2_bytes=64 * 1024)
    traffic = json.loads((BENCH / "traffic" / "hotcold-llc.json")
                         .read_text())
    sim = grid.simulator(cfg)
    sweep = grid.sweep_grid(cfg, traffic, 2 ** 31 + 17)
    rows = {b: sim.sweep(**sweep, backend=b)
            for b in ("reference", "pallas")}
    assert repr(rows["pallas"]) == repr(rows["reference"])
    # the sharded, streamed path pmaps the segment kernel
    sharded = sim.sweep(**sweep, backend="pallas", mesh=2,
                        stream_chunk=1024)
    assert repr(sharded) == repr(rows["reference"])
    want = reference.sweep_rows(cfg, traffic, 2 ** 31 + 17)
    values = compare.compare([rows["pallas"]], want)
    assert compare.passed(values), values
    assert 0 < rows["pallas"][0]["l2_miss_rate"] < 1


# ---------------------------------------------------------------------------
# streamed (segment carry) — incl. the satellite-2 regression: segment
# and chunk lengths that do NOT divide the trace, sentinel padding inert
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,segment,chunk", [
    (250, 77, 64),       # nothing divides anything
    (256, 256, 512),     # one segment, chunk > trace
    (300, 100, 32),      # segment multiple, chunk not
])
def test_streamed_parity_padding_invariance(n, segment, chunk):
    args = rand_trace(2, n, sentinel_tail=n // 5)
    ref = engine.run_traces(CACHE, *args)
    pal = engine.run_traces(CACHE, *args, backend="pallas", chunk=chunk,
                            segment=segment)
    assert_run_equal(pal, ref)


def test_stream_traces_pallas_backend():
    args = rand_trace(2, 333)
    ref = engine.run_traces(CACHE, *args)
    src = distribute.segment_batch(args, 128)
    got = distribute.stream_traces(CACHE, src, backend="pallas", chunk=64)
    assert_run_equal(got, ref)


def test_segment_carry_interchangeable_between_backends():
    # the SAME carry threads through either backend's segment step:
    # alternate per segment, end state must equal the pure reference run
    addr, wr, core, tier = rand_trace(2, 240, sentinel_tail=30)
    ref = engine.run_traces(CACHE, addr, wr, core, tier)
    carry = engine.init_batch_carry(CACHE, 2)
    for i, s in enumerate(range(0, 240, 80)):
        sl = slice(s, s + 80)
        carry = engine.run_batch_segment(
            CACHE, carry, addr[:, sl], wr[:, sl], core[:, sl],
            tier[:, sl], backend=("pallas" if i % 2 else "reference"),
            chunk=32)
    np.testing.assert_array_equal(np.asarray(carry[2]),
                                  np.asarray(ref[0]))


# ---------------------------------------------------------------------------
# the kernels' tie rules, one access at a time, against cache._step
# ---------------------------------------------------------------------------
# CACHE: per core an L1 of 16 sets x 2 ways, a shared L2 of 32 sets x 4
# ways; lines 0, 32, 64, 96 and 128 share L1 set 0 and L2 set 0.
def _equal_use_state():
    """L1 set 1 of core 0 and L2 set 1 full of live lines, every way
    last used at the same time: the LRU victim is the first way."""
    st = C.init_state(CACHE)
    l1 = st.l1_tag.at[0, 1].set(np.array([1, 17]))
    l2 = st.l2_tag.at[1].set(np.array([1, 17, 33, 49]))
    return st._replace(
        l1_tag=l1, l1_use=st.l1_use.at[0, 1].set(5),
        l1_state=st.l1_state.at[0, 1].set(np.array([C.E, C.S])),
        l2_tag=l2, l2_use=st.l2_use.at[1].set(5),
        l2_state=st.l2_state.at[1].set(C.E),
        l2_sharers=st.l2_sharers.at[1].set(np.array([1, 1, 0, 0])))


TIE_CASES = {
    # core 1's write leaves an invalid way below a valid one in core 0's
    # set; core 0 then misses into it, while the L2 set still has
    # several invalid ways of equal use
    "invalid_ways": (None, [(0, 0, 0), (16, 0, 0), (0, 1, 1), (32, 0, 0),
                            (48, 0, 0), (16, 0, 1), (0, 0, 0)]),
    # misses into full sets whose ways were all last used together
    "equal_use": (_equal_use_state, [(65, 0, 0), (81, 1, 0), (1, 0, 1),
                                     (97, 0, 1), (17, 1, 0)]),
    # a shared line written by the core that holds it S (an upgrade),
    # then by the other core (an RFO fill): each invalidates the other
    # copy
    "multicore_write": (None, [(5, 0, 0), (5, 0, 1), (5, 1, 1), (5, 1, 0),
                               (5, 0, 1), (21, 1, 0), (37, 0, 0)]),
    # core 0 holds line 0 dirty while core 1 pushes it out of L2: the
    # back-invalidation finds a dirty L1 victim and writes it back
    "dirty_back_invalidation": (None, [(0, 1, 0), (32, 0, 1), (64, 0, 1),
                                       (96, 1, 1), (128, 0, 1),
                                       (0, 0, 0)]),
}


@pytest.mark.parametrize("case", sorted(TIE_CASES))
def test_kernel_tie_rules_match_step(case):
    from repro.kernels import ops
    make_state, rows = TIE_CASES[case]
    st0 = C.init_state(CACHE) if make_state is None else make_state()
    addr, wr, core = (np.array([r[k] for r in rows], np.int32)
                      for k in range(3))
    tier = addr % CACHE.n_targets
    want_st, want = C.simulate_trace(CACHE, st0, addr, wr.astype(bool),
                                     core, tier)
    want_run = (np.asarray(want)[None], jax_tree_map(
        lambda x: np.asarray(x)[None], want_st))
    trace = [x[None] for x in (addr, wr, core, tier)]
    l1p, l2p = (x[None] for x in C.pack_state(st0))
    stats0 = np.zeros((1, C.nstats(CACHE.n_targets)), np.int32)
    t0 = np.ones((1,), np.int32)

    if make_state is None:     # the fresh-state kernel starts from init
        assert_run_equal(ops.mesi_cache_sim(*trace, params=CACHE, chunk=4),
                         want_run)
    l1s, l2s, stats, _ = ops.mesi_run_segment(
        (l1p, l2p, stats0, t0), *trace, params=CACHE, chunk=4)
    assert_run_equal((stats, C.unpack_state(l1s, l2s)), want_run)

    # the epoch kernel, on a static row (its page map never routes)
    one = np.ones((1,), np.int32)
    zero = 0 * one
    pages = 2
    carry = (l1p, l2p, stats0, t0, np.zeros((1, pages), np.int32),
             np.zeros((1, pages), np.int32),
             np.zeros((1, CACHE.n_targets), np.int32),
             np.zeros((1, CACHE.n_targets), np.int32), zero)
    (l1d, l2d, stats_d, *_), *_ = ops.mesi_dyn_segment(
        carry, *(x[:, None] for x in trace), zero, pages * one, zero, one,
        one, pages * one, zero, pages * one,
        np.zeros((1, pages, CACHE.n_targets), np.int32), zero, zero, zero,
        params=CACHE, k_max=1, count_bound=len(rows) + 1)
    assert_run_equal((stats_d, C.unpack_state(l1d, l2d)), want_run)


# ---------------------------------------------------------------------------
# dynamic tiering + sampled rows (sweep-level: full row dict equality)
# ---------------------------------------------------------------------------
DYN_AXIS = (None, DynamicTiering(epoch_len=512, budget=4, threshold=2))


def test_dynamic_tiering_sweep_parity():
    legacy = engine.run_sweep(spec(tiering=DYN_AXIS), CACHE, TIMING)
    rows = engine.run_sweep(spec("pallas", tiering=DYN_AXIS), CACHE,
                            TIMING)
    assert rows == legacy            # dict equality: floats to the bit


def test_sampled_sweep_parity():
    sampling = (None, SamplingSpec(warm_slots=1, measure_slots=2,
                                   period_slots=4))
    legacy = engine.run_sweep(
        spec(tiering=DYN_AXIS, sampling=sampling), CACHE, TIMING)
    rows = engine.run_sweep(
        spec("pallas", tiering=DYN_AXIS, sampling=sampling), CACHE,
        TIMING)
    assert rows == legacy


# ---------------------------------------------------------------------------
# the epoch kernel's segment against the scan's, shape by shape
# ---------------------------------------------------------------------------
SEGMENT_CASES = {
    # name: (rows, slots, slot, chunk, pages, row scalars)
    # a slot streamed as four SMEM trace blocks
    "four_blocks_a_slot": (2, 3, 256, 64, 20, {}),
    # blocks that do not divide the slot: its tail is sentinel padding
    "padded_last_block": (1, 3, 200, 96, 20, {}),
    # epochs of three slots: two slots in three are no boundary
    "epoch_of_three_slots": (2, 6, 64, 64, 20, dict(period=3, thr=4)),
    # 200 pages, two page rows of which 72 lanes pad, the last ten
    # pages not migration-eligible, DRAM capacity forcing demotions
    "pages_not_lane_aligned": (2, 4, 128, 128, 200,
                               dict(n_pages=190, cap=12)),
    # a budget beyond the hot pages: every hot page promotes
    "budget_over_hot_pages": (1, 4, 128, 128, 40, dict(budget=16)),
    # three tiers under a sampled window
    "three_tier_sampled": (1, 6, 64, 32, 20,
                           dict(ssd=True, l1cap=2, s_per=3)),
}


@pytest.mark.parametrize("case", sorted(SEGMENT_CASES))
def test_epoch_kernel_segment_matches_the_scan(case):
    """One segment of ``mesi_dyn_segment`` against the reference's
    ``run_dynamic_segment``: carries and per-slot outputs bitwise equal
    from a carry mid-run (pages on every level, a running clock and
    slot index, nonzero counters and migration totals)."""
    from repro.kernels import ops
    b, e, slot, chunk, n_p, kw = SEGMENT_CASES[case]
    rng = np.random.default_rng(len(case))
    n_t = CACHE.n_targets + 1 if kw.get("ssd") else CACHE.n_targets
    p = C.CacheParams(l1_bytes=2048, l1_ways=2, l2_bytes=8192, l2_ways=4,
                      cores=2, n_targets=n_t)
    shape = (b, e, slot)
    # 70% of accesses on four hot pages, the rest anywhere
    addr = np.where(rng.random(shape) < 0.7,
                    rng.integers(0, 4 * 64, shape),
                    rng.integers(0, n_p * 64, shape)).astype(np.int32)
    addr[-1, -1, slot // 2:] = engine.SENTINEL
    trace = [addr, rng.integers(0, 2, shape), rng.integers(0, 2, shape),
             rng.integers(1, n_t, shape)]
    one = np.ones((b,), np.int32)
    ssd_tid = n_t - 1 if kw.get("ssd") else 0
    scalars = [one, one * kw.get("n_pages", n_p), one * kw.get("budget", 4),
               one * kw.get("thr", 2), one * kw.get("period", 1),
               one * kw.get("cap", 1 << 30), one * ssd_tid,
               one * kw.get("l1cap", 1 << 30),
               rng.integers(0, 40, (b, n_p, n_t)), one, one * 2,
               one * kw.get("s_per", 0)]
    k_max = kw.get("budget", 4)
    count_bound = kw.get("period", 1) * slot + 1
    pmap0 = rng.integers(0, 3 if ssd_tid else 2, (b, n_p))
    carry = tiering_dyn.init_dyn_carry(p, pmap0)
    # start mid-run: one reference segment first
    carry, *_ = tiering_dyn.run_dynamic_segment(
        p, k_max, count_bound, carry, *(x[:, :1] for x in trace), *scalars)
    want = tiering_dyn.run_dynamic_segment(p, k_max, count_bound, carry,
                                           *trace, *scalars)
    got = ops.mesi_dyn_segment(carry, *trace, *scalars, params=p,
                               k_max=k_max, count_bound=count_bound,
                               chunk=chunk)
    names = ("l1p", "l2p", "stats", "t", "page_map", "counts", "mig_read",
             "mig_write", "eidx", "slots", "snapshots", "meas")
    for name, g, w in zip(names, [*got[0], *got[1:]], [*want[0], *want[1:]]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    slots = np.asarray(want[1])
    assert slots[..., 2].sum() > 0            # the case migrates
    if case == "budget_over_hot_pages":
        assert slots[..., 2].max() < kw["budget"]


# ---------------------------------------------------------------------------
# latency distributions + the CXL-SSD third tier (ISSUE 10)
# ---------------------------------------------------------------------------
SSD_TIERS = (None, DynamicTiering(epoch_len=512, budget=4, threshold=2,
                                  cxl_capacity_pages=4))
SSD_TOPO = (route_mod.direct(1, ssd_gib=16),)
DIST_AXIS = (None, LatencyDistribution(n_samples=128, seed=7))


def test_distribution_ssd_sweep_parity():
    # distribution timing and the SSD tier in one grid: every row —
    # percentile columns, SSD-target counters, off rows — bitwise-equal
    # across backends (the percentiles are host-side NumPy over integer
    # device stats, so parity of the stats implies parity of the tails)
    kw = dict(topologies=SSD_TOPO, tiering=SSD_TIERS,
              distributions=DIST_AXIS)
    legacy = engine.run_sweep(spec(**kw), CACHE, TIMING)
    rows = engine.run_sweep(spec("pallas", **kw), CACHE, TIMING)
    assert rows == legacy


def test_three_tier_checkpoint_cross_backend_resume(tmp_path):
    # a reference-run checkpoint of a three-tier (SSD-demoting) sweep
    # restores under pallas: the 9-tuple epoch carry is shared unchanged
    kw = dict(topologies=SSD_TOPO, tiering=SSD_TIERS)
    legacy = engine.run_sweep(spec(**kw), CACHE, TIMING)
    pol = distribute.resilience.CheckpointPolicy(tmp_path / "ckpt",
                                                 every_segments=1,
                                                 blocking=True)
    plan = FaultPlan((Fault("crash", shard=0, segment=1),))
    with pytest.raises(RunKilled):
        distribute.run_sweep(spec(**kw), CACHE, TIMING,
                             stream_chunk=1024, resume=pol,
                             fault_plan=plan)
    rows = distribute.run_sweep(spec("pallas", **kw), CACHE, TIMING,
                                stream_chunk=1024, resume=pol,
                                report=RunReport())
    assert rows == legacy


# ---------------------------------------------------------------------------
# sharded + streamed execution strategies
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mesh,stream_chunk", [
    (2, None), (None, 512), (2, 1024), (3, 768),
])
def test_sharded_sweep_parity(mesh, stream_chunk):
    legacy = engine.run_sweep(spec(tiering=DYN_AXIS), CACHE, TIMING)
    rows = distribute.run_sweep(spec("pallas", tiering=DYN_AXIS), CACHE,
                                TIMING, mesh=mesh,
                                stream_chunk=stream_chunk)
    assert rows == legacy


# ---------------------------------------------------------------------------
# resilience: the satellite-1 regression and kill-and-resume on pallas
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_nofault_resilient_equals_sharded(backend):
    # ResilientExecutor with no checkpoint and no fault plan must fall
    # through to plain sharded dispatch (no NotImplementedError, no
    # result change) on EVERY backend
    s = spec(backend)
    sharded = distribute.run_sweep(s, CACHE, TIMING, mesh=2,
                                   stream_chunk=1024)
    resilient = distribute.run_sweep(s, CACHE, TIMING, mesh=2,
                                     stream_chunk=1024,
                                     report=RunReport())
    assert resilient == sharded


def test_kill_and_resume_parity_pallas(tmp_path):
    legacy = engine.run_sweep(spec(tiering=DYN_AXIS), CACHE, TIMING)
    s = spec("pallas", tiering=DYN_AXIS)
    pol = distribute.resilience.CheckpointPolicy(tmp_path / "ckpt",
                                                 every_segments=1,
                                                 blocking=True)
    plan = FaultPlan((Fault("crash", shard=0, segment=1),))
    with pytest.raises(RunKilled):
        distribute.run_sweep(s, CACHE, TIMING, stream_chunk=1024,
                             resume=pol, fault_plan=plan)
    report = RunReport()
    rows = distribute.run_sweep(s, CACHE, TIMING, stream_chunk=1024,
                                resume=pol, report=report)
    assert rows == legacy
    assert report.summary()["fast_forwarded_segments"] >= 1


def test_checkpoint_written_by_reference_resumes_on_pallas(tmp_path):
    # same carry => a reference-run checkpoint restores under pallas
    legacy = engine.run_sweep(spec(tiering=DYN_AXIS), CACHE, TIMING)
    pol = distribute.resilience.CheckpointPolicy(tmp_path / "ckpt",
                                                 every_segments=1,
                                                 blocking=True)
    plan = FaultPlan((Fault("crash", shard=0, segment=1),))
    with pytest.raises(RunKilled):
        distribute.run_sweep(spec(tiering=DYN_AXIS), CACHE, TIMING,
                             stream_chunk=1024, resume=pol,
                             fault_plan=plan)
    rows = distribute.run_sweep(spec("pallas", tiering=DYN_AXIS), CACHE,
                                TIMING, stream_chunk=1024, resume=pol,
                                report=RunReport())
    assert rows == legacy

"""Fault-tolerant sweep runtime: recovery never changes a number.

The contract under test (ISSUE 6 acceptance): a sweep that is killed,
degraded, retried, or requeued produces **bitwise-identical**
`RunResult.row()` output to an uninterrupted run.  Concretely:

* kill-at-every-segment-boundary → `run_sweep(resume=...)` parity —
  static, dynamic-tiering, and sharded rows;
* an injected transient failure is retried with backoff and completes
  without changing any row; exhausting the retry budget raises
  `ResilienceError` cleanly;
* OOM degradation (segment halving) keeps parity; so does device
  eviction + shard requeue;
* checkpoints GC under `keep`, stale tmp dirs are swept, and restore
  validation raises real exceptions (treedef / shape / plan mismatch).

Everything runs on one CPU host via the deterministic `FaultPlan`
injector — no real failures required.
"""

import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointError, CheckpointManager
from repro.core import cache as C
from repro.core import distribute, engine, numa, resilience
from repro.core import route as route_mod
from repro.core.machine import CPUModel
from repro.core.resilience import (CheckpointPolicy, Fault, FaultPlan,
                                   ResilienceError, RetryPolicy, RunKilled,
                                   RunReport)
from repro.core.tiering_dyn import DynamicTiering
from repro.core.timing import TimingConfig

RNG = np.random.default_rng(23)

CACHE = C.CacheParams(l1_bytes=8 * 1024, l1_ways=2,
                      l2_bytes=16 * 1024, l2_ways=8)
TIMING = TimingConfig()
CPUS = (CPUModel(kind="o3", mlp=8),)
SEG = 512           # stream_chunk: 2048-access traces -> 4 segments


def grid_spec(**kw):
    """A small static grid (1 footprint x 2 policies x 2 topologies)."""
    base = dict(footprint_factors=(1,),
                policies=(numa.ZNuma(1.0), numa.WeightedInterleave(1, 1)),
                cpus=CPUS,
                topologies=(route_mod.direct(1), route_mod.direct(2)))
    base.update(kw)
    return engine.SweepSpec(**base)


def dyn_spec():
    """Static + dynamic tiering rows in one grid (epoch == SEG, so the
    streamed program also has 4 one-slot segments)."""
    return grid_spec(topologies=(route_mod.direct(2),),
                     tiering=(None, DynamicTiering(epoch_len=512,
                                                   budget=4)))


def policy(tmp_path, **kw):
    kw.setdefault("every_segments", 1)
    kw.setdefault("blocking", True)      # deterministic file counts
    return CheckpointPolicy(tmp_path / "ckpt", **kw)


def run_resilient(spec, *, mesh=None, resume=None, fault_plan=None,
                  retry=None, report=None, stream_chunk=SEG):
    return distribute.run_sweep(spec, CACHE, TIMING, mesh=mesh,
                                stream_chunk=stream_chunk, resume=resume,
                                fault_plan=fault_plan, retry=retry,
                                report=report)


# ---------------------------------------------------------------------------
# The resilient executor is an execution strategy, not a result change
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("spec_fn", [grid_spec, dyn_spec])
def test_resilient_executor_uninterrupted_parity(spec_fn):
    spec = spec_fn()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    rows = run_resilient(spec, report=RunReport())
    assert rows == legacy            # dict equality: floats to the bit


# ---------------------------------------------------------------------------
# Kill at EVERY segment boundary -> resume parity (the tentpole invariant)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("spec_fn,mesh", [
    (grid_spec, None),               # static rows, one shard
    (dyn_spec, None),                # dynamic-tiering rows
    (grid_spec, 2),                  # sharded static rows
])
def test_kill_at_every_boundary_resume_parity(tmp_path, spec_fn, mesh):
    spec = spec_fn()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    n_segments = 4                   # 4096-access traces / SEG
    for boundary in range(n_segments):
        pol = policy(tmp_path / f"b{boundary}")
        plan = FaultPlan((Fault("crash", shard=0, segment=boundary),))
        with pytest.raises(RunKilled):
            run_resilient(spec, mesh=mesh, resume=pol, fault_plan=plan)
        report = RunReport()
        rows = run_resilient(spec, mesh=mesh, resume=pol, report=report)
        assert rows == legacy, f"boundary={boundary}"
        if boundary > 0:             # something was actually fast-forwarded
            assert report.summary()["fast_forwarded_segments"] >= boundary


def test_resume_of_completed_run_is_pure_fast_forward(tmp_path):
    spec = grid_spec()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    pol = policy(tmp_path)
    assert run_resilient(spec, resume=pol) == legacy
    report = RunReport()
    assert run_resilient(spec, resume=pol, report=report) == legacy
    # every shard restores at its final segment: no checkpoint rewrites
    assert report.resumes == 1
    assert report.summary()["fast_forwarded_segments"] == 4
    assert report.checkpoints == 0


# ---------------------------------------------------------------------------
# Transient failures: bounded retry + backoff, then clean exhaustion
# ---------------------------------------------------------------------------
def test_transient_failure_retried_with_backoff_keeps_rows():
    spec = dyn_spec()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    sleeps = []
    report = RunReport()
    ex = distribute.ResilientExecutor(
        stream_chunk=SEG,
        fault_plan=FaultPlan((Fault("transient", shard=0, segment=1,
                                    count=2),)),
        retry=RetryPolicy(max_retries=3, backoff_s=0.5, backoff_factor=2.0),
        report=report, sleeper=sleeps.append)
    rows = engine.run_sweep(spec, CACHE, TIMING, executor=ex)
    assert rows == legacy
    assert report.retries == 2
    assert sleeps == [0.5, 1.0]      # exponential backoff, injectable sleep


def test_transient_retry_exhaustion_raises_cleanly():
    spec = grid_spec()
    ex = distribute.ResilientExecutor(
        stream_chunk=SEG,
        fault_plan=FaultPlan((Fault("transient", shard=0, segment=0,
                                    count=99),)),
        retry=RetryPolicy(max_retries=2, backoff_s=0.0),
        sleeper=lambda s: None)
    with pytest.raises(ResilienceError, match="retry budget exhausted"):
        engine.run_sweep(spec, CACHE, TIMING, executor=ex)


def test_seeded_random_transients_are_deterministic_and_survivable():
    spec = grid_spec()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    reports = []
    for _ in range(2):
        report = RunReport()
        ex = distribute.ResilientExecutor(
            stream_chunk=SEG,
            fault_plan=FaultPlan(seed=7, p_transient=0.5),
            retry=RetryPolicy(backoff_s=0.0), report=report,
            sleeper=lambda s: None)
        assert engine.run_sweep(spec, CACHE, TIMING, executor=ex) == legacy
        reports.append([e for e in report.events if e["event"] == "retry"])
    assert reports[0]                # p=0.5 over 4 sites: fires somewhere
    assert reports[0] == reports[1]  # same seed -> same fault sites


# ---------------------------------------------------------------------------
# OOM: degrade by halving, rerun from the intact carry, same numbers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("spec_fn", [grid_spec, dyn_spec])
def test_oom_degradation_parity(spec_fn):
    spec = spec_fn()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    report = RunReport()
    # width-triggered: every dispatch wider than 512 accesses OOMs, so
    # the 2048-access resident segment must halve twice (2048 -> 1024
    # -> 512) before calls go through — dynamic rows split on slot
    # boundaries (4 slots -> 2 -> 1), static rows on columns
    ex = distribute.ResilientExecutor(
        stream_chunk=2048,
        fault_plan=FaultPlan((Fault("oom", shard=0, oom_above=512),)),
        report=report)
    rows = engine.run_sweep(spec, CACHE, TIMING, executor=ex)
    assert rows == legacy
    assert report.degradations == 2


@pytest.mark.parametrize("spec_fn", [grid_spec, dyn_spec])
def test_resilient_segments_never_donate_the_carry(monkeypatch, spec_fn):
    """A retried or degraded segment re-runs from the carry it was given,
    so that carry must outlive the call.  Off the CPU a donated carry is
    deleted by the call (a v5e does so), whether or not the call fails."""
    from repro.core import tiering_dyn
    asked = []
    for mod, name in ((engine, "run_batch_segment"),
                      (tiering_dyn, "run_dynamic_segment")):
        def spy(*args, _fn=getattr(mod, name), **kw):
            asked.append(kw.get("donate", False))
            return _fn(*args, **kw)
        monkeypatch.setattr(mod, name, spy)
    run_resilient(spec_fn(), report=RunReport())
    assert asked and not any(asked)


def test_oom_at_minimum_width_raises():
    spec = grid_spec()
    ex = distribute.ResilientExecutor(
        stream_chunk=SEG,
        fault_plan=FaultPlan((Fault("oom", shard=0, oom_above=0),)),
        retry=RetryPolicy(max_halvings=3))
    with pytest.raises(ResilienceError, match="OOM persists"):
        engine.run_sweep(spec, CACHE, TIMING, executor=ex)


# ---------------------------------------------------------------------------
# Device loss: evict the host, requeue the shard, same numbers
# ---------------------------------------------------------------------------
def test_device_loss_evicts_and_requeues_with_parity():
    import jax
    spec = grid_spec()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    d0 = jax.local_devices()[0]
    report = RunReport()
    # two logical hosts on one physical device: shard 1's host dies
    ex = distribute.ResilientExecutor(
        mesh=distribute.Mesh(n_shards=2, devices=(d0, d0)),
        stream_chunk=SEG,
        fault_plan=FaultPlan((Fault("device_lost", shard=1, segment=0),)),
        report=report)
    rows = engine.run_sweep(spec, CACHE, TIMING, executor=ex)
    assert rows == legacy
    evicts = [e for e in report.events if e["event"] == "evict"]
    assert len(evicts) == 1 and evicts[0]["reason"] == "device_lost"


def test_losing_every_device_raises():
    import jax
    spec = grid_spec()
    d0 = jax.local_devices()[0]
    ex = distribute.ResilientExecutor(
        mesh=distribute.Mesh(n_shards=1, devices=(d0,)),
        stream_chunk=SEG,
        fault_plan=FaultPlan((Fault("device_lost", shard=0, segment=0,
                                    count=99),)))
    with pytest.raises(ResilienceError, match="no surviving devices"):
        engine.run_sweep(spec, CACHE, TIMING, executor=ex)


# ---------------------------------------------------------------------------
# Slow-shard injection: logged, never result-bearing
# ---------------------------------------------------------------------------
def test_slow_shard_is_logged_not_fatal():
    spec = grid_spec()
    legacy = engine.run_sweep(spec, CACHE, TIMING)
    report = RunReport()
    stalls = []
    ex = distribute.ResilientExecutor(
        stream_chunk=SEG,
        fault_plan=FaultPlan((Fault("slow", shard=0, segment=1,
                                    delay_s=7.5),)),
        report=report, sleeper=stalls.append)
    assert engine.run_sweep(spec, CACHE, TIMING, executor=ex) == legacy
    assert stalls == [7.5]
    assert report.count("slow") == 1


# ---------------------------------------------------------------------------
# stream_traces: checkpointed streaming fast-forwards on rerun
# ---------------------------------------------------------------------------
def test_stream_traces_checkpoint_resume_parity(tmp_path):
    b, n = 3, 4096
    addr = RNG.integers(0, 256, (b, n)).astype(np.int32)
    w = RNG.integers(0, 2, (b, n)).astype(np.int32)
    ref_stats, _ = engine.run_traces(CACHE, addr, w)
    pol = policy(tmp_path, every_segments=2)
    src = lambda: distribute.segment_batch((addr, w, None, None), 512)
    r1 = RunReport()
    s1, _ = distribute.stream_traces(CACHE, src(), checkpoint=pol,
                                     report=r1)
    assert np.array_equal(np.asarray(s1), np.asarray(ref_stats))
    assert r1.checkpoints == 4       # 8 segments / every 2
    r2 = RunReport()
    s2, _ = distribute.stream_traces(CACHE, src(), checkpoint=pol,
                                     report=r2)
    assert np.array_equal(np.asarray(s2), np.asarray(ref_stats))
    assert r2.summary()["fast_forwarded_segments"] == 8
    assert r2.checkpoints == 0       # nothing re-ran, nothing re-saved


# ---------------------------------------------------------------------------
# Checkpoint hygiene: GC under keep, stale tmp sweep, real validation
# ---------------------------------------------------------------------------
def test_checkpoint_gc_respects_keep(tmp_path):
    pol = policy(tmp_path, keep=2)
    run_resilient(grid_spec(), resume=pol)
    shard_dirs = sorted(pol.directory.glob("shard_*"))
    assert shard_dirs, "no per-shard checkpoints written"
    for sd in shard_dirs:
        steps = sorted(p.name for p in sd.glob("step_*"))
        assert len(steps) <= 2, f"{sd}: {steps}"
        assert steps[-1] == "step_000004"    # the final carry survives GC


def test_manager_sweeps_stale_tmp_dirs(tmp_path):
    stale = tmp_path / "tmp_step_000007"
    stale.mkdir(parents=True)
    (stale / "leaf_00000.npy").write_bytes(b"garbage")
    CheckpointManager(tmp_path)
    assert not stale.exists()


def test_manager_restore_validates_treedef_and_shape(tmp_path):
    m = CheckpointManager(tmp_path)
    m.save(3, {"a": np.arange(4)})
    with pytest.raises(CheckpointError, match="treedef mismatch"):
        m.restore(3, {"b": {"nested": np.arange(4)}})
    with pytest.raises(CheckpointError, match="stored shape"):
        m.restore(3, {"a": np.arange(5)})
    step, tree = m.restore(3, {"a": np.zeros(4, np.int64)})
    assert step == 3 and tree["a"].tolist() == [0, 1, 2, 3]


def test_resume_refuses_a_different_execution_plan(tmp_path):
    pol = policy(tmp_path)
    run_resilient(grid_spec(), resume=pol)
    with pytest.raises(ResilienceError, match="different execution plan"):
        run_resilient(grid_spec(), resume=pol, stream_chunk=1024)


# ---------------------------------------------------------------------------
# FaultPlan / RunReport unit behavior
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("msg,kind", [
    ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate 8.00G.",
     "oom"),
    ("RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
     "allocate 32.00G. That was not possible. There are 15.75G free.",
     "oom"),
    # too wide for HBM at compile time: a narrower segment may fit
    ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
     "memory in memory space hbm.", "oom"),
    ("INTERNAL: Mosaic failed to compile TPU kernel: unsupported op",
     "fatal"),
    ("INTERNAL: XLA:TPU compile permanent error. Unsupported layout.",
     "fatal"),
    ("UNIMPLEMENTED: no lowering for this op", "fatal"),
    ("INVALID_ARGUMENT: shape mismatch", "fatal"),
    ("UNAVAILABLE: TPU device is resetting", "transient"),
    ("INTERNAL: stream did not block host until done", "transient"),
    ("INTERNAL: the compiled program's launch timed out", "transient"),
])
def test_classify_real_xla_errors(msg, kind):
    """Compiler refusals and wrong programs are never retried; running
    out of HBM, at compile time too, degrades."""
    import jax
    assert resilience.classify_failure(jax.errors.JaxRuntimeError(msg)) \
        == kind


def test_fault_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("meteor", shard=0)
    with pytest.raises(ValueError, match="count"):
        Fault("crash", shard=0, count=0)
    with pytest.raises(ValueError, match="p_transient"):
        FaultPlan(p_transient=1.5)


def test_fault_count_is_per_site_and_bounded():
    plan = FaultPlan((Fault("transient", shard=0, segment=1, count=2),))
    for _ in range(2):
        with pytest.raises(resilience.TransientDeviceError):
            plan.check(0, 1)
    plan.check(0, 1)                 # exhausted: third attempt passes
    plan.check(1, 1)                 # other shards never fire
    plan.check(0, 0)


def test_report_summary_counts():
    r = RunReport()
    r.add("retry", shard=0, segment=1, attempt=1, backoff_s=0.1)
    r.add("checkpoint", shard=0, segments_done=2, elapsed_s=0.25,
          blocking=True)
    r.add("resume", shard=0, fast_forward_segments=3, elapsed_s=0.1)
    s = r.summary()
    assert s["retries"] == 1
    assert s["checkpoints"] == 1
    assert s["fast_forwarded_segments"] == 3
    assert s["checkpoint_s_max"] == 0.25

"""Sharded + streaming sweep executor: past one device, past one memory.

The batched trace engine (:mod:`repro.core.engine`) compiles a whole
characterization grid into ONE vmapped device program — which caps both
the grid size (every stacked trace resident at once) and the trace
length (one scan over the whole thing) at a single accelerator's memory.
This module scales the same engine along both axes without changing a
single simulated number:

**Sharding** (`Mesh`)
    The flattened sweep grid — tiering x topologies x workloads x
    footprints x policies, already deduplicated into batch rows by
    `engine.build_sweep_batch` — is partitioned row-wise into shards.
    Shards are padded with all-sentinel rows so every shard has the same
    shape (ragged grids compile exactly one program), mapped over the
    mesh devices with :func:`jax.pmap` in super-steps of
    ``len(devices)`` shards, and dispatched **asynchronously**: the host
    enqueues every super-step before blocking once at the end, so
    host-side result accumulation overlaps device compute and transfer.
    Rows are simulated independently (the vmap carries no cross-row
    state), so sharded stats are **bitwise-equal** to the one-program
    path — test-enforced, including dynamic-tiering rows.

**Streaming** (`stream_chunk` / :func:`stream_traces`)
    The trace axis is cut into fixed-size segments threaded through the
    scan carry (`engine.init_batch_carry` / `engine.run_batch_segment`;
    dynamic-tiering rows thread the full tierer carry — page map, epoch
    counters, migration totals, slot index — via
    `tiering_dyn.run_dynamic_segment`, i.e. the epoch-slot machinery
    rides the segment carry).  Only one segment plus the carry is ever
    resident on device, with the carry buffers donated between calls on
    non-CPU backends, so trace lengths beyond device memory run in
    bounded memory.  Segmentation is bitwise-neutral (integer state
    machine, exact carry hand-off).

Single-device / single-program fallback: ``mesh=None`` with
``stream_chunk=None`` is *the* legacy path (the executor seam defaults
to `engine.LocalExecutor`), so results are bitwise-equal to the
pre-executor engine by construction — and the golden fixtures pin it.

See ``docs/scaling.md`` for the design discussion and knob guide.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Iterable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_mod
from repro.core import engine
from repro.core import resilience
from repro.core import tiering_dyn
from repro.core.engine import SENTINEL, SweepSpec, TraceBatch
from repro.core.machine import RunResult
from repro.core.resilience import (CheckpointPolicy, FaultPlan, RetryPolicy,
                                   RunReport, SweepCheckpointer)
from repro.core.timing import TimingConfig
from repro.runtime.fault import FleetState

Array = jax.Array


# ---------------------------------------------------------------------------
# Mesh: where the shards go
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Mesh:
    """Row-partition plan for a sweep batch.

    Parameters
    ----------
    n_shards : int
        How many row-shards to cut the batch into.  ``0`` (default) =
        one shard per device — the natural data-parallel layout.  More
        shards than devices run in super-steps of ``len(devices)``
        (useful on a single device to bound the per-program batch, or
        to overlap async dispatch with host accumulation).
    devices : tuple of jax.Device, optional
        The devices to map shards onto; ``None`` = all
        :func:`jax.local_devices`.

    Notes
    -----
    Shards never change results: rows are simulated independently, so
    any partition yields bitwise-identical stats (test-enforced).  On a
    1-device host a multi-shard mesh still runs every shard — it just
    serializes the super-steps, which is why the shard-scaling benchmark
    documents a flat-line there.
    """
    n_shards: int = 0
    devices: Optional[Tuple] = None

    def __post_init__(self) -> None:
        if self.n_shards < 0:
            raise ValueError(f"n_shards must be >= 0, got {self.n_shards}")

    def resolve_devices(self) -> Tuple:
        return (tuple(self.devices) if self.devices
                else tuple(jax.local_devices()))

    def shard_count(self, b: int) -> int:
        """Shards actually cut for a ``b``-row batch (never more than b)."""
        n = self.n_shards if self.n_shards > 0 \
            else len(self.resolve_devices())
        return max(1, min(n, b))


def auto_mesh() -> Mesh:
    """One shard per local device — the default multi-device layout."""
    return Mesh()


def _as_mesh(mesh) -> Optional[Mesh]:
    """Accept `Mesh`, an int shard count, or None."""
    if mesh is None or isinstance(mesh, Mesh):
        return mesh
    if isinstance(mesh, int):
        return Mesh(n_shards=mesh)
    raise TypeError(f"mesh must be a Mesh, int, or None, got {type(mesh)}")


# ---------------------------------------------------------------------------
# Shard arithmetic
# ---------------------------------------------------------------------------
def shard_plan(b: int, n_shards: int) -> Tuple[int, int]:
    """Rows-per-shard and padded row count for ``b`` rows over shards.

    Returns ``(rows_per_shard, b_padded)`` with ``b_padded = n_shards *
    rows_per_shard >= b``; the ``b_padded - b`` filler rows are
    all-sentinel traces whose stats are identically zero (padding-row
    invariance is test-enforced).
    """
    if b < 1:
        raise ValueError("empty batch")
    rows = -(-b // n_shards)
    return rows, rows * n_shards


def _pad_rows(x: Array, b_to: int, fill: int) -> Array:
    """Append `fill`-valued rows so the (B, ...) array has `b_to` rows."""
    b = x.shape[0]
    if b == b_to:
        return x
    pad = jnp.full((b_to - b,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad], axis=0)


def trace_working_set_bytes(b: int, n: int, fields: int = 4,
                            itemsize: int = 4) -> int:
    """Device bytes a resident (B, N) stacked trace occupies.

    Four int32 streams per row (addr, is_write, core, tier).  The
    streaming path's working set is ``trace_working_set_bytes(b,
    segment)`` plus the carry, regardless of total trace length.
    """
    return b * n * fields * itemsize


# ---------------------------------------------------------------------------
# pmap super-step: one shard per device, carry threaded between segments
# ---------------------------------------------------------------------------
def _kernel_segment(p: cache_mod.CacheParams, carry, addr: Array,
                    is_write: Array, core: Array, tier: Array, *,
                    chunk: int):
    """The Pallas segment kernel with the reference step's signature."""
    from repro.kernels import ops
    return ops.mesi_run_segment(carry, addr, is_write, core, tier,
                                params=p, chunk=chunk)


@functools.lru_cache(maxsize=None)
def _pmap_stepper(devices: Tuple, donate: bool, backend: str, chunk: int):
    """pmap of one backend's segment step, pinned to `devices`.

    One cached instance per (devices, donate, backend, chunk): the mapped
    axis is the super-step's shards, placed on exactly the mesh's devices
    (not whatever `jax.local_devices()` order would pick).  The reference
    scan's carry buffers are donated between streamed segments off-CPU,
    so only one carry is ever resident per shard; the kernel re-lays the
    carry out into its planes, so it has no buffer to reuse.
    """
    if backend == "reference":
        step = engine._run_batch_segment_impl
    else:
        step = functools.partial(_kernel_segment, chunk=chunk)
        donate = False
    return jax.pmap(step, static_broadcasted_argnums=(0,),
                    donate_argnums=(1,) if donate else (),
                    devices=devices)


def _pmap_segment(p: cache_mod.CacheParams, devices: Tuple, carry,
                  addr: Array, is_write: Array, core: Array, tier: Array,
                  *, backend: str = "reference", chunk: int = 512):
    """Advance each device's shard by one trace segment (mapped axis =
    shards of this super-step, one per entry of `devices`) on the
    resolved `backend`: the reference scan or the Pallas segment kernel,
    which thread the same carry."""
    donate = jax.default_backend() != "cpu"
    return _pmap_stepper(devices, donate, backend, chunk)(
        p, carry, addr, is_write, core, tier)


def _reshape_shards(x: Array, g: int) -> Array:
    """(g*bp, ...) -> (g, bp, ...) for the pmap's mapped leading axis."""
    return x.reshape((g, x.shape[0] // g) + x.shape[1:])


# ---------------------------------------------------------------------------
# Streaming: segments through the scan carry
# ---------------------------------------------------------------------------
def segment_batch(batch_or_arrays, segment: int
                  ) -> Iterator[Tuple[Array, Array, Array, Array]]:
    """Slice a resident stacked trace into (B, segment) streaming tuples.

    Accepts a :class:`~repro.core.engine.TraceBatch` or an ``(addr,
    is_write, core, tier)`` tuple of (B, N) arrays; the final slice is
    sentinel-padded to the full segment length (inert).  This is the
    parity-testing source — a real beyond-memory run generates each
    segment on the fly instead (any iterable of tuples works, see
    :func:`stream_traces`).
    """
    if isinstance(batch_or_arrays, TraceBatch):
        arrays = (batch_or_arrays.addr, batch_or_arrays.is_write,
                  batch_or_arrays.core, batch_or_arrays.tier)
    else:
        arrays = batch_or_arrays
    addr = jnp.asarray(arrays[0], jnp.int32)
    b, n = addr.shape
    z = jnp.zeros((b, n), jnp.int32)
    rest = [z if a is None else jnp.asarray(a, jnp.int32)
            for a in arrays[1:]]
    fills = (SENTINEL, 0, 0, 0)
    for s in range(0, n, segment):
        e = min(s + segment, n)
        out = []
        for a, fill in zip((addr, *rest), fills):
            sl = a[:, s:e]
            if e - s < segment:
                sl = jnp.concatenate(
                    [sl, jnp.full((b, segment - (e - s)), fill,
                                  jnp.int32)], axis=1)
            out.append(sl)
        yield tuple(out)


def stream_traces(p: cache_mod.CacheParams,
                  source: Iterable[Tuple], *,
                  checkpoint=None,
                  report: Optional[RunReport] = None,
                  backend: Optional[str] = None,
                  chunk: int = 512,
                  ) -> Tuple[Array, cache_mod.CacheState]:
    """Consume a trace as a stream of fixed-size segments, bounded memory.

    Parameters
    ----------
    p : CacheParams
        Cache geometry.
    source : iterable of (addr, is_write, core, tier) tuples
        Each a (B, n_seg) int32 segment (``None`` fields become zeros;
        ``addr == SENTINEL`` marks padding).  Segments should share one
        length — each distinct length compiles its own program.  The
        source may *generate* segments lazily (a generator that builds
        each slice on demand), which is what lets total trace length
        exceed device memory: only one segment plus the scan carry is
        ever resident, and the carry buffers are donated between calls
        on non-CPU backends.
    checkpoint : CheckpointPolicy, path, or None
        Persist the scan carry every
        :attr:`~repro.core.resilience.CheckpointPolicy.every_segments`
        consumed segments; a rerun against the same directory (with a
        deterministically regenerable ``source``) **fast-forwards**
        past the already-completed segments without a single device
        call and produces bitwise-identical results (test-enforced).
    report : RunReport, optional
        Event sink for ``resume`` / ``checkpoint`` records.
    backend : {"reference", "pallas", None}
        Segment stepper: the vmapped reference scan or the Pallas
        segment kernel — both thread the same ``(l1p, l2p, stats, t)``
        carry and are bitwise-equal (test-enforced).  ``None``: the
        platform's (:func:`repro.core.engine.resolve_backend`).
    chunk : int
        Pallas kernel inner chunk length (ignored by the reference
        backend).

    Returns
    -------
    (stats, state)
        Exactly :func:`repro.core.engine.run_traces`'s return — and
        bitwise-equal to it on the concatenated trace (test-enforced).
    """
    policy = resilience.as_checkpoint_policy(checkpoint)
    ckpt: Optional[SweepCheckpointer] = None
    carry = None
    done = 0
    idx = 0
    for seg in source:
        addr = jnp.asarray(seg[0], jnp.int32)
        if carry is None:
            carry = engine.init_batch_carry(p, addr.shape[0])
            if policy is not None:
                ckpt = SweepCheckpointer(policy)
                ckpt.verify_meta({"kind": "stream",
                                  "b": int(addr.shape[0]),
                                  "n_targets": p.n_targets})
                got = ckpt.restore(0, {"carry": resilience.host_tree(carry)},
                                   report=report)
                if got is not None:
                    done, tree = got
                    carry = tree["carry"]
        idx += 1
        if idx <= done:
            continue        # fast-forward: replayed segments cost no call
        z = jnp.zeros(addr.shape, jnp.int32)
        fields = [z if (len(seg) <= i or seg[i] is None)
                  else jnp.asarray(seg[i], jnp.int32) for i in (1, 2, 3)]
        carry = engine.run_batch_segment(p, carry, addr, *fields,
                                         donate=True, backend=backend,
                                         chunk=chunk)
        if ckpt is not None and idx % policy.every_segments == 0:
            ckpt.save(0, idx, {"carry": resilience.host_tree(carry)},
                      report=report)
    if carry is None:
        raise ValueError("empty trace source")
    if ckpt is not None:
        ckpt.wait()
    l1p, l2p, stats, _ = carry
    return stats, cache_mod.unpack_state(l1p, l2p)


# ---------------------------------------------------------------------------
# The sharded executor (plugs into engine.run_sweep's executor seam)
# ---------------------------------------------------------------------------
class ShardedExecutor:
    """Execute a built sweep batch sharded across a mesh and/or streamed.

    Drop-in for :class:`repro.core.engine.LocalExecutor` — same
    ``run_static`` / ``run_dynamic`` contract, bitwise-identical
    counters (test-enforced), different execution strategy:

    * rows are cut into ``mesh.shard_count(B)`` equal shards (sentinel
      padding rows square off ragged grids),
    * each super-step pmaps ``len(devices)`` shards and is dispatched
      without blocking — the final gather blocks once, so transfer and
      host accumulation overlap compute,
    * with ``stream_chunk``, every shard's trace streams through the
      scan carry in ``stream_chunk``-sized segments (dynamic-tiering
      rows stream whole epoch slots: the chunk is rounded to the sweep's
      slot length).

    Parameters
    ----------
    mesh : Mesh, int, or None
        Row partition; int = shard count; ``None`` = no sharding.
    stream_chunk : int, optional
        Trace elements per streamed segment; ``None`` = resident traces.
    """

    def __init__(self, mesh=None, stream_chunk: Optional[int] = None):
        if stream_chunk is not None and stream_chunk < 1:
            raise ValueError(
                f"stream_chunk must be >= 1, got {stream_chunk}")
        self.mesh = _as_mesh(mesh)
        self.stream_chunk = stream_chunk

    # -- static (flat-scan) rows -------------------------------------------
    def run_static(self, p: cache_mod.CacheParams, batch: TraceBatch,
                   *, backend: str, chunk: int) -> np.ndarray:
        addr = jnp.asarray(batch.addr, jnp.int32)
        b, n = addr.shape
        z = jnp.zeros((b, n), jnp.int32)
        is_write = (z if batch.is_write is None
                    else jnp.asarray(batch.is_write, jnp.int32))
        core = z if batch.core is None else jnp.asarray(batch.core,
                                                        jnp.int32)
        tier = z if batch.tier is None else jnp.asarray(batch.tier,
                                                        jnp.int32)
        mesh = self.mesh or Mesh(n_shards=1)
        n_shards = mesh.shard_count(b)
        bp, b_pad = shard_plan(b, n_shards)
        addr = _pad_rows(addr, b_pad, SENTINEL)
        is_write = _pad_rows(is_write, b_pad, 0)
        core = _pad_rows(core, b_pad, 0)
        tier = _pad_rows(tier, b_pad, 0)
        seg = self.stream_chunk if self.stream_chunk is not None else n
        seg = min(seg, n)       # never pad beyond the trace itself
        n_pad = -(-n // seg) * seg
        addr = engine._pad_to_segment(addr, n_pad, SENTINEL)
        is_write = engine._pad_to_segment(is_write, n_pad, 0)
        core = engine._pad_to_segment(core, n_pad, 0)
        tier = engine._pad_to_segment(tier, n_pad, 0)
        devices = mesh.resolve_devices()
        d = len(devices)
        outs: List[Array] = []
        for g0 in range(0, n_shards, d):
            g = min(d, n_shards - g0)
            rows = slice(g0 * bp, (g0 + g) * bp)
            sh = [_reshape_shards(a[rows], g)
                  for a in (addr, is_write, core, tier)]
            carry = jax.tree_util.tree_map(
                lambda x: _reshape_shards(x, g),
                engine.init_batch_carry(p, g * bp))
            for s in range(0, n_pad, seg):
                carry = _pmap_segment(p, devices[:g], carry,
                                      *(a[:, :, s:s + seg] for a in sh),
                                      backend=backend, chunk=chunk)
            # stats only; enqueue without blocking — super-steps overlap
            outs.append(carry[2].reshape(g * bp, -1))
        jax.block_until_ready(outs)
        stats = np.concatenate([np.asarray(o) for o in outs], axis=0)
        return stats[:b].astype(np.int64)

    # -- dynamic (epoch-structured) rows -----------------------------------
    def run_dynamic(self, p: cache_mod.CacheParams, tb,
                    *, slot_len: int, k_max: int,
                    backend: str = "reference"):
        """Shard the epoch program row-wise; stream whole epoch slots.

        Padding rows are inert static rows (all-sentinel trace, zero
        budget), so the padded program's real rows are bitwise-equal to
        the one-program path; per-row outputs are concatenated and the
        padding dropped.  ``stream_chunk`` streams ``max(1, chunk //
        slot_len)`` slots per segment — the tierer carry (page map,
        counters, migration totals, slot index) threads between
        segments.
        """
        batch = tb.batch
        b = batch.batch
        mesh = self.mesh or Mesh(n_shards=1)
        n_shards = mesh.shard_count(b)
        bp, b_pad = shard_plan(b, n_shards)
        seg_slots = (None if self.stream_chunk is None
                     else max(1, self.stream_chunk // slot_len))
        addr = _pad_rows(jnp.asarray(batch.addr, jnp.int32), b_pad,
                         SENTINEL)
        z = jnp.zeros(addr.shape, jnp.int32)
        others = [z if a is None else _pad_rows(jnp.asarray(a, jnp.int32),
                                                b_pad, 0)
                  for a in (batch.is_write, batch.core, batch.tier)]
        scal = {
            "dyn_flag": _pad_rows(jnp.asarray(tb.dyn_flag, jnp.int32),
                                  b_pad, 0),
            "page_map0": _pad_rows(jnp.asarray(tb.page_map0, jnp.int32),
                                   b_pad, 1),
            "n_pages": _pad_rows(jnp.asarray(tb.n_pages, jnp.int32),
                                 b_pad, 1),
            "budget": _pad_rows(jnp.asarray(tb.budget, jnp.int32),
                                b_pad, 0),
            "threshold": _pad_rows(jnp.asarray(tb.threshold, jnp.int32),
                                   b_pad, 1),
            "period": _pad_rows(jnp.asarray(tb.period, jnp.int32),
                                b_pad, 1),
            "dram_cap": _pad_rows(jnp.asarray(tb.dram_cap, jnp.int32),
                                  b_pad, engine._UNBOUNDED_PAGES),
            "ssd_tid": _pad_rows(jnp.asarray(tb.ssd_tid, jnp.int32),
                                 b_pad, 0),
            "cxl_cap": _pad_rows(jnp.asarray(tb.cxl_cap, jnp.int32),
                                 b_pad, engine._UNBOUNDED_PAGES),
            "page_target_lines": _pad_rows(
                jnp.asarray(tb.page_target_lines, jnp.int32), b_pad, 0),
            # sampling window scalars: zero fill = measure-every-slot
            # (padding rows never reach the results anyway)
            "s_warm": _pad_rows(jnp.asarray(tb.s_warm, jnp.int32),
                                b_pad, 0),
            "s_meas": _pad_rows(jnp.asarray(tb.s_meas, jnp.int32),
                                b_pad, 0),
            "s_per": _pad_rows(jnp.asarray(tb.s_per, jnp.int32),
                               b_pad, 0),
        }
        devices = mesh.resolve_devices()
        outs = []
        for i, s0 in enumerate(range(0, b_pad, bp)):
            rows = slice(s0, s0 + bp)
            dev = devices[i % len(devices)]    # round-robin shard placement
            args = [jax.device_put(a[rows], dev)
                    for a in (addr, *others)]
            out = tiering_dyn.run_dynamic(
                p, *args, slot_len=slot_len, k_max=k_max,
                segment_slots=seg_slots, backend=backend,
                **{k: jax.device_put(v[rows], dev)
                   for k, v in scal.items()})
            outs.append(out)
        jax.block_until_ready(outs)
        # gather on the host: the shards live on different devices
        return tiering_dyn.DynOutputs(*(
            np.concatenate([np.asarray(getattr(o, f)) for o in outs],
                           axis=0)[:b]
            for f in tiering_dyn.DynOutputs._fields))


# ---------------------------------------------------------------------------
# The resilient executor: checkpoints, retries, degradation, eviction
# ---------------------------------------------------------------------------
class ResilientExecutor:
    """Fault-tolerant sweep execution on the same executor seam.

    Drop-in for :class:`~repro.core.engine.LocalExecutor` /
    :class:`ShardedExecutor` — same ``run_static`` / ``run_dynamic``
    contract, bitwise-identical counters (test- and golden-enforced) —
    that survives the failure modes a week-long sweep meets in practice:

    * **crash / kill** — every shard's scan carry is checkpointed every
      ``checkpoint.every_segments`` completed segments (atomic, async,
      keep-K via :class:`~repro.core.resilience.SweepCheckpointer`); a
      rerun against the same directory restores each shard's newest
      carry and fast-forwards past the completed segments without a
      single device call;
    * **transient device errors** — each segment dispatch retries with
      exponential backoff (:class:`~repro.core.resilience.RetryPolicy`),
      raising :class:`~repro.core.resilience.ResilienceError` only when
      the budget is exhausted;
    * **OOM** — the failing shard's segments are halved (re-dispatched
      as two half-width calls from the intact pre-segment carry, and
      again on repeat) up to ``retry.max_halvings`` times — segment
      boundaries are bitwise-neutral, so degraded rows are identical;
    * **device loss** — the losing logical host is evicted from a
      :class:`repro.runtime.fault.FleetState` (the training runtime's
      eviction bookkeeping, reused) and the shard requeues onto the
      next surviving device.

    Shards run sequentially per dispatch (recovery needs per-shard
    carries), which changes *strategy*, never *results* — rows are
    simulated independently and the per-access arithmetic is exactly
    the engine's segment step.  Both backends work: the Pallas segment
    kernel threads the same carry the reference scan does, so
    checkpoint/resume replays it bitwise-identically (test-enforced).
    With no checkpoint and no fault plan the static path falls through
    to plain sharded dispatch — the recovery scaffolding costs nothing
    when there is nothing to recover.

    Every recovery action lands in :attr:`report`
    (:class:`~repro.core.resilience.RunReport`); injected failures come
    from an optional :class:`~repro.core.resilience.FaultPlan`, making
    all of the above deterministic and testable on one CPU host.

    Parameters
    ----------
    mesh : Mesh, int, or None
        Row partition (also the logical host pool for eviction);
        ``None`` = one shard.
    stream_chunk : int, optional
        Trace elements per streamed segment — also the checkpoint and
        recovery granularity.  ``None`` = one segment per trace
        (checkpoint only at completion).
    checkpoint : CheckpointPolicy, path, or None
        Where/how often to persist carries; a bare path uses the
        policy defaults.  ``None`` disables persistence (retry/OOM
        recovery still work from in-memory carries).
    fault_plan : FaultPlan, optional
        Deterministic failure injection (tests, chaos drills).
    retry : RetryPolicy, optional
        Backoff and degradation bounds.
    report : RunReport, optional
        Event sink; a fresh one is created when omitted.
    sleeper : callable
        Injectable ``time.sleep`` (tests pass a recorder).
    """

    def __init__(self, mesh=None, stream_chunk: Optional[int] = None, *,
                 checkpoint=None, fault_plan: Optional[FaultPlan] = None,
                 retry: Optional[RetryPolicy] = None,
                 report: Optional[RunReport] = None,
                 sleeper=time.sleep):
        if stream_chunk is not None and stream_chunk < 1:
            raise ValueError(
                f"stream_chunk must be >= 1, got {stream_chunk}")
        self.mesh = _as_mesh(mesh)
        self.stream_chunk = stream_chunk
        self.checkpoint = resilience.as_checkpoint_policy(checkpoint)
        self.fault_plan = fault_plan
        self.retry = retry if retry is not None else RetryPolicy()
        self.report = report if report is not None else RunReport()
        self.sleeper = sleeper

    # -- shared recovery machinery -----------------------------------------
    def _checkpointer(self, meta: dict) -> Optional[SweepCheckpointer]:
        if self.checkpoint is None:
            return None
        ckpt = SweepCheckpointer(self.checkpoint)
        ckpt.verify_meta(meta)
        return ckpt

    def _fleet_devices(self):
        mesh = self.mesh or Mesh(n_shards=1)
        devices = mesh.resolve_devices()
        return mesh, devices, FleetState(n_hosts=len(devices))

    def _shard_device(self, shard: int, fleet: FleetState, devices):
        live = fleet.live_hosts()
        if not live:
            raise resilience.ResilienceError(
                "no surviving devices: every logical host was evicted")
        return live[shard % len(live)], devices[live[shard % len(live)]]

    def _dispatch(self, shard: int, segment: int, width: int,
                  fleet: FleetState, devices, call):
        """Run one device call under the full recovery policy.

        ``call()`` is re-invoked on transient errors (bounded retry,
        exponential backoff) and after device eviction; OOM and crash
        propagate to the caller (the segment loop owns degradation, the
        user owns resume).  Returns ``call()``'s value.
        """
        attempts = 0
        while True:
            try:
                if self.fault_plan is not None:
                    self.fault_plan.check(shard, segment, width=width,
                                          report=self.report,
                                          sleeper=self.sleeper)
                return call()
            except Exception as exc:     # RunKilled (BaseException) flies
                kind = resilience.classify_failure(exc)
                if kind == "fatal":
                    raise
                if kind == "oom":
                    raise               # the segment loop halves + reruns
                if kind == "device_lost":
                    host, _ = self._shard_device(shard, fleet, devices)
                    fleet.evict(host, "device_lost",
                                log=self.report.events)
                    # requeue onto a survivor; does not spend a retry
                    self._shard_device(shard, fleet, devices)
                    continue
                if attempts >= self.retry.max_retries:
                    raise resilience.ResilienceError(
                        f"retry budget exhausted ({self.retry.max_retries}"
                        f" retries) at shard {shard}, segment {segment}"
                    ) from exc
                backoff = self.retry.backoff(attempts)
                self.report.add("retry", shard=shard, segment=segment,
                                attempt=attempts + 1, backoff_s=backoff,
                                error=str(exc))
                self.sleeper(backoff)
                attempts += 1

    def _run_segment_degraded(self, shard: int, segment: int, carry,
                              halvings: List[int], fleet, devices,
                              units: int, unit_elems: int, advance):
        """One top-level segment with OOM degradation.

        ``advance(carry, lo, hi)`` advances the carry over the
        ``[lo, hi)`` sub-slice of the segment's ``units`` (trace
        columns for static rows, epoch slots for dynamic rows —
        ``unit_elems`` trace elements per unit).  On OOM the whole
        segment re-runs from the intact pre-segment carry in twice as
        many pieces — sub-splitting is bitwise-neutral, so the degraded
        result is identical.  The per-shard halving level sticks
        (later segments stay degraded).
        """
        seg_carry = carry
        while True:
            pieces = 1 << halvings[shard]
            step = max(1, -(-units // pieces))
            try:
                carry = seg_carry
                for lo in range(0, units, step):
                    hi = min(lo + step, units)
                    carry = self._dispatch(
                        shard, segment, (hi - lo) * unit_elems, fleet,
                        devices,
                        lambda c=carry, lo=lo, hi=hi: advance(c, lo, hi))
                return carry
            except Exception as exc:
                if resilience.classify_failure(exc) != "oom":
                    raise
                if step <= 1 or halvings[shard] >= self.retry.max_halvings:
                    raise resilience.ResilienceError(
                        f"OOM persists at minimum segment width (shard "
                        f"{shard}, segment {segment}, "
                        f"{halvings[shard]} halvings)") from exc
                halvings[shard] += 1
                self.report.add("degrade", shard=shard, segment=segment,
                                halvings=halvings[shard],
                                pieces=1 << halvings[shard])

    # -- static (flat-scan) rows -------------------------------------------
    def run_static(self, p: cache_mod.CacheParams, batch: TraceBatch,
                   *, backend: str, chunk: int) -> np.ndarray:
        if (backend != "reference" and self.checkpoint is None
                and self.fault_plan is None):
            # nothing to checkpoint, nothing to inject: plain sharded
            # dispatch (bitwise-equal — the carry loop below would only
            # add per-segment host round-trips)
            return ShardedExecutor(
                mesh=self.mesh, stream_chunk=self.stream_chunk
            ).run_static(p, batch, backend=backend, chunk=chunk)
        addr = jnp.asarray(batch.addr, jnp.int32)
        b, n = addr.shape
        z = jnp.zeros((b, n), jnp.int32)
        fields = [z if a is None else jnp.asarray(a, jnp.int32)
                  for a in (batch.is_write, batch.core, batch.tier)]
        mesh, devices, fleet = self._fleet_devices()
        n_shards = mesh.shard_count(b)
        bp, b_pad = shard_plan(b, n_shards)
        addr = _pad_rows(addr, b_pad, SENTINEL)
        fields = [_pad_rows(a, b_pad, 0) for a in fields]
        seg = min(self.stream_chunk or n, n)
        n_pad = -(-n // seg) * seg
        addr = engine._pad_to_segment(addr, n_pad, SENTINEL)
        fields = [engine._pad_to_segment(a, n_pad, 0) for a in fields]
        n_segments = n_pad // seg
        ckpt = self._checkpointer({
            "kind": "static", "b": b, "n": n, "n_shards": n_shards,
            "segment": seg, "n_targets": p.n_targets})
        halvings = [0] * n_shards
        outs: List[np.ndarray] = []
        for shard in range(n_shards):
            rows = slice(shard * bp, (shard + 1) * bp)
            sh = [a[rows] for a in (addr, *fields)]
            carry = engine.init_batch_carry(p, bp)
            start = 0
            if ckpt is not None:
                like = {"carry": resilience.host_tree(carry)}
                got = ckpt.restore(shard, like, report=self.report)
                if got is not None:
                    start, tree = got
                    carry = tree["carry"]

            def advance(c, lo, hi, sh=sh, shard=shard, s0=0):
                # placement follows the shard's current host (requeued
                # shards land on a survivor); donate=False so a failed
                # call leaves `c` intact for the retry
                _, dev = self._shard_device(shard, fleet, devices)
                args = [jax.device_put(a[:, s0 + lo:s0 + hi], dev)
                        for a in sh]
                return engine.run_batch_segment(
                    p, jax.device_put(c, dev), *args, donate=False,
                    backend=backend, chunk=chunk)

            for si in range(start, n_segments):
                carry = self._run_segment_degraded(
                    shard, si, carry, halvings, fleet, devices, seg, 1,
                    functools.partial(advance, s0=si * seg))
                done = si + 1
                if ckpt is not None and (
                        done % self.checkpoint.every_segments == 0
                        or done == n_segments):
                    ckpt.save(shard, done,
                              {"carry": resilience.host_tree(carry)},
                              report=self.report)
            outs.append(np.asarray(jax.block_until_ready(carry[2])))
        if ckpt is not None:
            ckpt.wait()
        stats = np.concatenate(outs, axis=0)
        return stats[:b].astype(np.int64)

    # -- dynamic (epoch-structured) rows -----------------------------------
    def run_dynamic(self, p: cache_mod.CacheParams, tb,
                    *, slot_len: int, k_max: int,
                    backend: str = "reference"):
        batch = tb.batch
        b = batch.batch
        mesh, devices, fleet = self._fleet_devices()
        n_shards = mesh.shard_count(b)
        bp, b_pad = shard_plan(b, n_shards)
        addr = _pad_rows(jnp.asarray(batch.addr, jnp.int32), b_pad,
                         SENTINEL)
        z = jnp.zeros(addr.shape, jnp.int32)
        others = [z if a is None else _pad_rows(jnp.asarray(a, jnp.int32),
                                                b_pad, 0)
                  for a in (batch.is_write, batch.core, batch.tier)]
        # padding rows are inert static rows — same fills as the
        # sharded executor, so padded programs share its invariance
        a3, w3, c3, t3, pmap0, scalars, k_max, count_bound = \
            tiering_dyn.prep_dynamic_inputs(
                addr, *others, slot_len=slot_len, k_max=k_max,
                dyn_flag=_pad_rows(jnp.asarray(tb.dyn_flag, jnp.int32),
                                   b_pad, 0),
                page_map0=_pad_rows(jnp.asarray(tb.page_map0, jnp.int32),
                                    b_pad, 1),
                n_pages=_pad_rows(jnp.asarray(tb.n_pages, jnp.int32),
                                  b_pad, 1),
                budget=_pad_rows(jnp.asarray(tb.budget, jnp.int32),
                                 b_pad, 0),
                threshold=_pad_rows(jnp.asarray(tb.threshold, jnp.int32),
                                    b_pad, 1),
                period=_pad_rows(jnp.asarray(tb.period, jnp.int32),
                                 b_pad, 1),
                dram_cap=_pad_rows(jnp.asarray(tb.dram_cap, jnp.int32),
                                   b_pad, engine._UNBOUNDED_PAGES),
                ssd_tid=_pad_rows(jnp.asarray(tb.ssd_tid, jnp.int32),
                                  b_pad, 0),
                cxl_cap=_pad_rows(jnp.asarray(tb.cxl_cap, jnp.int32),
                                  b_pad, engine._UNBOUNDED_PAGES),
                page_target_lines=_pad_rows(
                    jnp.asarray(tb.page_target_lines, jnp.int32),
                    b_pad, 0),
                s_warm=_pad_rows(jnp.asarray(tb.s_warm, jnp.int32),
                                 b_pad, 0),
                s_meas=_pad_rows(jnp.asarray(tb.s_meas, jnp.int32),
                                 b_pad, 0),
                s_per=_pad_rows(jnp.asarray(tb.s_per, jnp.int32),
                                b_pad, 0))
        e = a3.shape[1]
        seg_slots = (e if self.stream_chunk is None
                     else min(max(1, self.stream_chunk // slot_len), e))
        n_segments = -(-e // seg_slots)
        nstats = cache_mod.nstats(p.n_targets)
        ckpt = self._checkpointer({
            "kind": "dynamic", "b": b, "slots": e, "slot_len": slot_len,
            "n_shards": n_shards, "segment_slots": seg_slots,
            "n_targets": p.n_targets})
        halvings = [0] * n_shards
        outs = []
        for shard in range(n_shards):
            rows = slice(shard * bp, (shard + 1) * bp)
            xs = [a[rows] for a in (a3, w3, c3, t3)]
            sc = [s[rows] for s in scalars]
            carry = tiering_dyn.init_dyn_carry(p, pmap0[rows])
            # host accumulators keep the checkpoint tree shape-stable:
            # completed segments fill their slice, the rest stays zero
            acc = resilience.dyn_accumulators(bp, e, nstats)
            start = 0
            if ckpt is not None:
                like = {"carry": resilience.host_tree(carry), **acc}
                got = ckpt.restore(shard, like, report=self.report)
                if got is not None:
                    start, tree = got
                    carry = tree["carry"]
                    acc = {k: tree[k] for k in acc}

            def advance(c, lo, hi, xs=xs, sc=sc, shard=shard, s0=0,
                        acc=acc):
                _, dev = self._shard_device(shard, fleet, devices)
                args = [jax.device_put(a[:, s0 + lo:s0 + hi], dev)
                        for a in xs]
                c, slots, snaps, meas = tiering_dyn.run_dynamic_segment(
                    p, k_max, count_bound, jax.device_put(c, dev),
                    *args, *sc, donate=False, backend=backend)
                sl = slice(s0 + lo, s0 + hi)
                acc["slots"][:, sl] = np.asarray(slots)
                acc["snaps"][:, sl] = np.asarray(snaps)
                acc["meas"][:, sl] = np.asarray(meas)
                return c

            for si in range(start, n_segments):
                s0 = si * seg_slots
                width = min(seg_slots, e - s0)
                carry = self._run_segment_degraded(
                    shard, si, carry, halvings, fleet, devices, width,
                    slot_len, functools.partial(advance, s0=s0))
                done = si + 1
                if ckpt is not None and (
                        done % self.checkpoint.every_segments == 0
                        or done == n_segments):
                    ckpt.save(shard, done,
                              {"carry": resilience.host_tree(carry),
                               **acc},
                              report=self.report)
            jax.block_until_ready(carry)
            _, _, stats, _, pmap_f, _, mig_rd, mig_wr, _ = carry
            outs.append(tiering_dyn.DynOutputs(
                np.asarray(stats), np.asarray(pmap_f), np.asarray(mig_rd),
                np.asarray(mig_wr), acc["slots"], acc["snaps"],
                acc["meas"]))
        if ckpt is not None:
            ckpt.wait()
        return tiering_dyn.DynOutputs(*(
            np.concatenate([getattr(o, f) for o in outs], axis=0)[:b]
            for f in tiering_dyn.DynOutputs._fields))


# ---------------------------------------------------------------------------
# Facade: the sharded/streaming twins of engine.run_sweep
# ---------------------------------------------------------------------------
def run_sweep(spec: SweepSpec, cache: cache_mod.CacheParams,
              timing: TimingConfig, *, mesh=None,
              stream_chunk: Optional[int] = None,
              chunk: int = 512, resume=None,
              fault_plan: Optional[FaultPlan] = None,
              retry: Optional[RetryPolicy] = None,
              report: Optional[RunReport] = None) -> List[dict]:
    """`engine.run_sweep` with sharding, streaming and resilience knobs.

    Parameters
    ----------
    spec, cache, timing, chunk
        As in :func:`repro.core.engine.run_sweep`.
    mesh : Mesh, int, or None
        Row partition across devices.  ``None`` (with ``stream_chunk``
        also ``None``) is **exactly** the legacy single-program path —
        same executor, bitwise-equal rows (golden-fixture enforced).
    stream_chunk : int, optional
        Stream every trace through the scan carry in segments of this
        many accesses (bounded device memory per program).
    resume : CheckpointPolicy, path, or None
        Checkpoint directory for the :class:`ResilientExecutor`: scan
        carries persist every
        :attr:`~repro.core.resilience.CheckpointPolicy.every_segments`
        segments, and a rerun against the same directory fast-forwards
        past completed segments and shards — with rows bitwise-equal to
        an uninterrupted run (test- and golden-enforced).
    fault_plan : FaultPlan, optional
        Deterministic failure injection; any of the resilience knobs
        (``resume`` / ``fault_plan`` / ``retry`` / ``report``) selects
        the :class:`ResilientExecutor`.
    retry : RetryPolicy, optional
        Retry/backoff/degradation bounds.
    report : RunReport, optional
        Event sink for retries, resumes, degradations, checkpoints.

    Returns
    -------
    list of dict
        Identical rows — schema and values — to `engine.run_sweep` for
        any mesh/chunk/resilience choice (test-enforced).
    """
    executor = _executor_for(mesh, stream_chunk, resume=resume,
                             fault_plan=fault_plan, retry=retry,
                             report=report)
    return engine.run_sweep(spec, cache, timing, chunk=chunk,
                            executor=executor)


def sweep_results(spec: SweepSpec, cache: cache_mod.CacheParams,
                  timing: TimingConfig, *, mesh=None,
                  stream_chunk: Optional[int] = None,
                  chunk: int = 512, resume=None,
                  fault_plan: Optional[FaultPlan] = None,
                  retry: Optional[RetryPolicy] = None,
                  report: Optional[RunReport] = None) -> List[RunResult]:
    """`engine.sweep_results` with sharding/streaming/resilience knobs
    (see :func:`run_sweep`)."""
    executor = _executor_for(mesh, stream_chunk, resume=resume,
                             fault_plan=fault_plan, retry=retry,
                             report=report)
    return engine.sweep_results(spec, cache, timing, chunk=chunk,
                                executor=executor)


def _executor_for(mesh, stream_chunk, resume=None, fault_plan=None,
                  retry=None, report=None):
    if any(k is not None for k in (resume, fault_plan, retry, report)):
        return ResilientExecutor(mesh=mesh, stream_chunk=stream_chunk,
                                 checkpoint=resume, fault_plan=fault_plan,
                                 retry=retry, report=report)
    if mesh is None and stream_chunk is None:
        return None                     # engine.LocalExecutor: legacy path
    return ShardedExecutor(mesh=mesh, stream_chunk=stream_chunk)

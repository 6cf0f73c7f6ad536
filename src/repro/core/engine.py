"""Batched trace engine: multi-config characterization as ONE device program.

The paper's §IV suite sweeps STREAM footprints x page-placement policies x
CPU models.  The seed drove that sweep from Python — one `lax.scan` dispatch
(and one XLA compilation per trace length) per configuration.  This engine
stacks every (workload, topology, footprint, policy) configuration into a
leading batch dimension, pads the traces to a common length with sentinel
entries, and runs the *exact* two-level MESI model of
:mod:`repro.core.cache` under a single ``jax.vmap``-over-``lax.scan``
jitted program: one compilation, one device call for the whole suite.  CPU
models do not touch cache state, so the engine simulates each cell once and
broadcasts the stats across the CPU axis before closing the vectorized
Picard timing fixed point (:func:`repro.core.machine.time_batch`).

Traces come from the on-device workload generators of
:mod:`repro.workloads` (STREAM, pointer chase, GUPS, LLM KV-decode, MoE
expert streaming): pure jax ops produce each `(addr, is_write[, tier])`
stream directly on device, and :func:`stack_device_traces` pads/stacks
them there too — the host only ever sees shape metadata.

Sentinel convention
-------------------
Padded trace entries carry ``addr == SENTINEL`` (= -1).  The masked step
(:func:`repro.core.cache._gated_step`) and both Pallas kernels skip all
state/stat updates for them, so stats over a padded trace are **bitwise
equal** to the unpadded sequential run.  Padding is only ever appended at
the end of a trace (logical time still advances across sentinels).

Backends
--------
``reference``
    vmapped `lax.scan` over :func:`repro.core.cache._gated_step` — the
    oracle, and the fast path on CPU hosts.
``pallas``
    :func:`repro.kernels.ops.mesi_cache_sim` — the full two-level MESI +
    tier state machine with VMEM-resident tags, a (batch, chunks) grid and
    chunked HBM->SMEM trace streaming.  First-class across the whole sweep
    matrix: the carry-exposing segment kernels
    (:func:`repro.kernels.ops.mesi_run_segment`,
    :func:`repro.kernels.ops.mesi_dyn_segment`) drive dynamic tiering,
    sampling, segmented streaming, sharding and checkpoint/resume with
    bitwise parity to the reference (test-enforced by
    tests/test_backend_parity.py).  Compiled on TPU backends; interpret
    mode elsewhere (parity validation — keep geometries small).

``backend=None``, the default everywhere, resolves by platform
(:func:`resolve_backend`): the static and the epoch program run their
compiled kernels on a TPU, where one row's state fits the chip's VMEM,
and the reference scan elsewhere.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_mod
from repro.core import numa as numa_mod
from repro.core import obs
from repro.core import route as route_mod
from repro.core import sampling as sampling_mod
from repro.core import tiering_dyn
from repro.core.machine import CPUModel, RunResult, time_batch
from repro.core.timing import LatencyDistribution, TimingConfig

if TYPE_CHECKING:  # deferred at runtime: workloads builds on core
    from repro.workloads.base import Workload

Array = jax.Array

SENTINEL = cache_mod.SENTINEL   # padded trace entries: addr == SENTINEL
BACKENDS = ("reference", "pallas")


def resolve_backend(backend: Optional[str],
                    params: Optional[cache_mod.CacheParams] = None, *,
                    epoch: bool = False, n_pages: int = 0) -> str:
    """The implementation a program runs: ``backend`` when given, else
    the platform's.

    ``None`` resolves to the compiled Pallas kernel where jitted calls
    run on a TPU (the default device's platform) and the kernel's blocks
    for one batch row fit the chip's VMEM
    (:func:`repro.kernels.cache_sim.fits_chip` of ``params``, when
    given; for the epoch program, ``epoch=True``, with its ``n_pages``
    page planes), and to the reference scan on any other platform and
    for a larger cache.  An explicit name is only checked:
    ``'reference'`` always runs the scan, and ``'pallas'`` where it
    cannot compile raises.
    """
    if backend is None:
        from repro.kernels import cache_sim, ops
        if ops.platform() != "tpu":
            return "reference"
        fits = params is None or cache_sim.fits_chip(
            params, n_pages=n_pages if epoch else None)
        return "pallas" if fits else "reference"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; pick from {BACKENDS}")
    return backend


# ---------------------------------------------------------------------------
# Sweep specification
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """The characterization grid, batched into one device program.

    The cache model runs once per (workload, topology, footprint, policy)
    cell; `cpus` only vary the analytic timing layer.

    Parameters
    ----------
    footprint_factors : tuple of int
        Multiples of the machine's L2 size (the paper runs STREAM at
        {2,4,6,8} x L2); each workload scales its working set to
        ``k * l2_bytes``.
    policies : tuple of numa.Policy
        Page-placement policies (ignored by workloads that carry their own
        residency map, e.g. ``kv_decode``).
    cpus : tuple of CPUModel
        Analytic issue models; broadcast over the simulated cells.
    kernel : str
        STREAM kernel of the default workload axis (legacy knob; only used
        when `workloads` is empty).
    backend : str or None
        ``'reference'`` (vmapped scan), ``'pallas'`` (MESI kernel) or
        ``None``: each program's platform default (:func:`resolve_backend`).
    topologies : tuple of route.TopologySpec
        Scenario axis #1: each spec is enumerated (committed HDM decoders)
        and its N-target route map drives per-access routing — e.g. one
        direct-attach card, two interleaved cards, four endpoints behind a
        switch, all in the same vmapped device program (stats padded to
        the widest target count).  Empty = the legacy binary DRAM/CXL tier
        path, bitwise-identical to a single direct-attach expander
        (test-enforced).
    workloads : tuple of workloads.Workload
        Scenario axis #2: on-device trace generators
        (:mod:`repro.workloads`) — pointer chase, GUPS, KV-decode, MoE
        streaming, STREAM.  Empty = ``(Stream(kernel),)``, the legacy
        STREAM-only grid (bitwise-identical rows).
    tiering : tuple of Optional[tiering_dyn.DynamicTiering]
        Scenario axis #3: epoch-based dynamic tiering
        (:mod:`repro.core.tiering_dyn`).  ``None`` entries run static
        placement — bitwise-equal to the legacy rows (test-enforced) —
        while dynamic entries carry the page→tier map as scan state,
        promote/demote at epoch boundaries and charge migration traffic
        into the timing fixed point.  Mixed static/dynamic axes still
        run as ONE vmapped device program.  Empty = static only.
    sampling : tuple of Optional[sampling.SamplingSpec]
        Scenario axis #4: SMARTS-style sampled simulation
        (:mod:`repro.core.sampling`).  ``None`` entries run exact —
        bitwise-equal to the legacy rows (test-enforced) — while
        sampled entries alternate functional-warming slots (cache/tier
        state updated, stat accumulation masked) with detailed
        measurement windows, then scale the window stats to whole-trace
        estimates with CLT confidence intervals (``*_ci95`` /
        ``sampled_frac`` row columns).  Mixed exact/sampled axes still
        run as ONE vmapped device program.  Empty = exact only.
    distributions : tuple of Optional[timing.LatencyDistribution]
        Scenario axis #5: load-dependent latency *distributions*
        (:class:`repro.core.timing.LatencyDistribution`).  The axis only
        varies the analytic timing layer — like `cpus`, the device
        program runs ONCE and each entry re-closes the Picard fixed
        point, ``None`` entries bitwise-identical to the legacy
        deterministic rows (test-enforced) and distribution entries
        adding per-target ``lat_<t>_p50/p95/p99_ns`` row columns from
        counter-seeded stratified sampling (bitwise-reproducible across
        backends and runs).  Empty = deterministic point timing only.
    """
    footprint_factors: Tuple[int, ...] = (2, 4, 6, 8)
    policies: Tuple[numa_mod.Policy, ...] = (numa_mod.ZNuma(1.0),)
    cpus: Tuple[CPUModel, ...] = (CPUModel(kind="o3"),)
    kernel: str = "triad"
    backend: Optional[str] = None
    topologies: Tuple[route_mod.TopologySpec, ...] = ()
    workloads: Tuple["Workload", ...] = ()
    tiering: Tuple[Optional[tiering_dyn.DynamicTiering], ...] = ()
    sampling: Tuple[Optional[sampling_mod.SamplingSpec], ...] = ()
    distributions: Tuple[Optional[LatencyDistribution], ...] = ()

    @property
    def workload_axis(self) -> Tuple["Workload", ...]:
        """The workload loop; defaults to STREAM with `self.kernel`."""
        if self.workloads:
            return self.workloads
        from repro import workloads as wl_mod  # deferred: wl builds on core
        return (wl_mod.Stream(self.kernel),)

    @property
    def sim_cells(self) -> List[Tuple["Workload", int, numa_mod.Policy]]:
        """All (workload, footprint-factor, policy) cells, workload-major."""
        return [(wl, k, pol) for wl in self.workload_axis
                for k in self.footprint_factors
                for pol in self.policies]

    @property
    def topology_axis(self) -> Tuple[Optional[route_mod.TopologySpec], ...]:
        """The topology loop: `(None,)` = legacy binary-tier path."""
        return self.topologies if self.topologies else (None,)

    @property
    def tiering_axis(self) -> Tuple[
            Optional[tiering_dyn.DynamicTiering], ...]:
        """The tiering loop: `(None,)` = static placement only."""
        return self.tiering if self.tiering else (None,)

    @property
    def sampling_axis(self) -> Tuple[
            Optional[sampling_mod.SamplingSpec], ...]:
        """The sampling loop: `(None,)` = exact simulation only."""
        return self.sampling if self.sampling else (None,)

    @property
    def distributions_axis(self) -> Tuple[
            Optional[LatencyDistribution], ...]:
        """The latency-distribution loop: `(None,)` = point timing."""
        return self.distributions if self.distributions else (None,)


# ---------------------------------------------------------------------------
# Trace batching
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TraceBatch:
    """Stacked per-config traces, sentinel-padded to a common length.

    All arrays are (B, N) int32; `n_valid[b]` real entries per row, the rest
    sentinel-padded (`addr == SENTINEL`, other fields zero).
    """
    addr: np.ndarray
    is_write: np.ndarray
    core: np.ndarray
    tier: np.ndarray
    n_valid: np.ndarray

    @property
    def batch(self) -> int:
        return self.addr.shape[0]

    @property
    def length(self) -> int:
        return self.addr.shape[1]

    @property
    def total_accesses(self) -> int:
        return int(self.n_valid.sum())


def stack_traces(traces: Sequence[Tuple[np.ndarray, np.ndarray,
                                        Optional[np.ndarray],
                                        Optional[np.ndarray]]],
                 pad_to_multiple: int = 1) -> TraceBatch:
    """Stack (addr, is_write[, core[, tier]]) traces of unequal length.

    Rows are padded at the end with `SENTINEL` addresses (zero for the other
    fields); the common length is rounded up to `pad_to_multiple` so the
    Pallas backend can stream fixed-size chunks without a remainder.

    Parameters
    ----------
    traces : sequence of (addr, is_write[, core[, tier]]) tuples
        Host (NumPy) per-config traces; `None` fields become zeros.
    pad_to_multiple : int
        Chunk granularity the common length is rounded up to.

    Returns
    -------
    TraceBatch
        Host-resident `(B, N)` arrays.  See :func:`stack_device_traces`
        for the device-resident twin the workload generators use.
    """
    if not traces:
        raise ValueError("no traces to stack (empty sweep grid?)")
    n_valid = np.asarray([np.asarray(t[0]).shape[0] for t in traces],
                         np.int64)
    n_max = int(n_valid.max())
    n_max = -(-n_max // pad_to_multiple) * pad_to_multiple
    b = len(traces)
    addr = np.full((b, n_max), SENTINEL, np.int32)
    is_write = np.zeros((b, n_max), np.int32)
    core = np.zeros((b, n_max), np.int32)
    tier = np.zeros((b, n_max), np.int32)
    for i, t in enumerate(traces):
        a = np.asarray(t[0], np.int32)
        n = a.shape[0]
        addr[i, :n] = a
        is_write[i, :n] = np.asarray(t[1], np.int32)
        if len(t) > 2 and t[2] is not None:
            core[i, :n] = np.asarray(t[2], np.int32)
        if len(t) > 3 and t[3] is not None:
            tier[i, :n] = np.asarray(t[3], np.int32)
    return TraceBatch(addr=addr, is_write=is_write, core=core, tier=tier,
                      n_valid=n_valid)


def stack_device_traces(traces: Sequence[Tuple], pad_to_multiple: int = 1
                        ) -> TraceBatch:
    """Device-resident :func:`stack_traces`: pad + stack with `jnp` ops.

    The on-device workload generators (:mod:`repro.workloads`) produce
    their traces as `jax` arrays; this stacker keeps them on device — the
    sentinel padding and the `(B, N)` batch are built with `jnp`
    concatenate/stack, so no trace is ever materialized host-side.

    Parameters
    ----------
    traces : sequence of (addr, is_write[, core[, tier]]) tuples
        Per-config device traces (`None` fields become zeros).
    pad_to_multiple : int
        Chunk granularity the common length is rounded up to.

    Returns
    -------
    TraceBatch
        `(B, N)` device arrays; `n_valid` stays host-side (static shape
        metadata).
    """
    if not traces:
        raise ValueError("no traces to stack (empty sweep grid?)")
    n_valid = np.asarray([int(t[0].shape[0]) for t in traces], np.int64)
    n_max = int(n_valid.max())
    n_max = -(-n_max // pad_to_multiple) * pad_to_multiple

    def pad(x, n, fill):
        x = jnp.asarray(x, jnp.int32)
        if n == n_max:
            return x
        return jnp.concatenate([x, jnp.full((n_max - n,), fill, jnp.int32)])

    def field(i, fill=0):
        return jnp.stack([
            pad(t[i], int(n_valid[j]), fill)
            if len(t) > i and t[i] is not None
            else jnp.zeros((n_max,), jnp.int32)
            for j, t in enumerate(traces)])

    return TraceBatch(addr=field(0, fill=SENTINEL), is_write=field(1),
                      core=field(2), tier=field(3), n_valid=n_valid)


# ---------------------------------------------------------------------------
# Batched simulation: segment-carry primitives
# ---------------------------------------------------------------------------
# The batched scan is expressed as *segments threaded through an explicit
# carry*: `init_batch_carry` builds the per-row packed cache state, and
# `run_batch_segment` advances every row by one (B, n_seg) slice of the
# trace.  The resident path (`_run_batch_reference`) is simply ONE segment
# spanning the whole trace; the streaming executor
# (:mod:`repro.core.distribute`) feeds fixed-size segments one device call
# at a time so arbitrarily long traces run in bounded memory.  Because the
# cache model is integer arithmetic and the carry threads the exact scan
# state (including the logical clock `t`), splitting a trace into segments
# is **bitwise-neutral** (test-enforced by tests/test_distribute.py).

@functools.partial(jax.jit, static_argnums=(0, 1))
def init_batch_carry(p: cache_mod.CacheParams, b: int):
    """Fresh batched scan carry: `(l1p, l2p, stats, t)`, leading axis `b`.

    The carry layout is exactly what `cache._packed_step` threads:
    packed L1/L2 planes, the per-row stats vector, and the logical clock
    (which starts at 1, matching the sequential oracle).
    """
    l1p, l2p = cache_mod.pack_state(cache_mod.init_state(p))
    bcast = lambda x: jnp.broadcast_to(x[None], (b,) + x.shape)
    return (bcast(l1p), bcast(l2p),
            jnp.zeros((b, cache_mod.nstats(p.n_targets)), jnp.int32),
            jnp.ones((b,), jnp.int32))


def _run_batch_segment_impl(p: cache_mod.CacheParams, carry, addr: Array,
                            is_write: Array, core: Array, tier: Array):
    """Advance the batched carry over one (B, n_seg) trace segment.

    Uses the packed-state step (`cache._packed_step`) — bitwise-equal to
    the `_step` oracle but with one write per hierarchy update instead of
    ~24 vmapped scatters per access, which is what makes the batched
    program faster per access than the sequential loop on CPU.  `unroll=2`
    shaves the scan's loop overhead (larger unrolls regress on CPU).
    """
    valid = addr != SENTINEL

    def one(c, a, w, co, tr, v):
        c, _ = jax.lax.scan(functools.partial(cache_mod._packed_step, p),
                            c, (a, w, co, tr, v), unroll=2)
        return c

    return jax.vmap(one)(carry, addr, is_write.astype(bool),
                         core, tier, valid)


@functools.lru_cache(maxsize=None)
def _segment_stepper(donate: bool):
    """Jitted segment step; the carry buffers are donated off-CPU.

    Donation lets XLA reuse the previous carry's buffers in the streaming
    loop (no 2x state residency); CPU backends ignore donation and warn,
    so it is only requested elsewhere.
    """
    return jax.jit(_run_batch_segment_impl, static_argnums=(0,),
                   donate_argnums=(1,) if donate else ())


def run_batch_segment(p: cache_mod.CacheParams, carry, addr, is_write,
                      core, tier, *, donate: bool = False,
                      backend: Optional[str] = None, chunk: int = 512):
    """One streamed segment: `(carry, (B, n_seg) slice) -> carry`.

    Parameters
    ----------
    p : CacheParams
        Cache geometry (static under jit).
    carry : tuple
        `(l1p, l2p, stats, t)` from :func:`init_batch_carry` or a prior
        segment call.
    addr, is_write, core, tier : (B, n_seg) int32 arrays
        The segment; `addr == SENTINEL` marks padding.
    donate : bool
        Donate the carry buffers to the call (streaming loops off-CPU);
        the caller must not reuse the donated carry afterwards.
    backend : str or None
        'reference' (vmapped scan segment), 'pallas'
        (:func:`repro.kernels.ops.mesi_run_segment`) or None, the
        platform's (:func:`resolve_backend`).  Both thread the identical
        carry, so segments may alternate backends freely with
        bitwise-equal results (test-enforced).
    chunk : int
        Trace elements per Pallas grid step (pallas backend only).

    Returns
    -------
    tuple
        The advanced carry; `carry[2]` is the running (B, nstats) stats.
    """
    if resolve_backend(backend, p) == "pallas":
        from repro.kernels import ops
        return ops.mesi_run_segment(carry, addr, is_write, core, tier,
                                    params=p, chunk=chunk)
    donate = donate and jax.default_backend() != "cpu"
    return _segment_stepper(donate)(p, carry, addr, is_write, core, tier)


@functools.partial(jax.jit, static_argnums=0)
def _run_batch_reference(p: cache_mod.CacheParams, addr: Array,
                         is_write: Array, core: Array, tier: Array):
    """vmap-over-scan: the whole batch in one XLA program.

    Expressed as a single segment spanning the whole trace through the
    segment-carry primitives above — the streaming path runs the same
    per-access arithmetic, so segmented and resident stats are bitwise
    equal.
    """
    carry = init_batch_carry(p, addr.shape[0])
    l1p, l2p, stats, _ = _run_batch_segment_impl(p, carry, addr, is_write,
                                                 core, tier)
    return stats, cache_mod.unpack_state(l1p, l2p)


def run_traces(p: cache_mod.CacheParams, addr, is_write,
               core=None, tier=None, *, backend: Optional[str] = None,
               chunk: int = 512, segment: Optional[int] = None,
               ) -> Tuple[Array, cache_mod.CacheState]:
    """Simulate a (B, N) batch of sentinel-padded traces in one device call.

    Args:
      p: cache geometry (shared across the batch — it is static state
        layout; per-config *traces/tiers/policies* are what vary).
      addr: (B, N) int32, `SENTINEL` marks padding.
      is_write/core/tier: (B, N) int32 (or None for zeros).
      backend: 'reference' (vmapped scan), 'pallas' (MESI kernel) or
        None, the platform's (:func:`resolve_backend`).
      chunk: trace elements per Pallas grid step.
      segment: stream the trace through the scan carry in (B, segment)
        slices — one device call per slice instead of one program over
        the whole length (either backend; the pallas kernel advances the
        same carry via :func:`repro.kernels.ops.mesi_run_segment`).  The
        trace is sentinel-padded up to a multiple; stats and final state
        are bitwise-equal to the resident path (test-enforced).

    Returns: (stats (B, nstats(p.n_targets)) int32, batched CacheState).
    """
    with obs.span("sweep.prep") as sp:
        addr = jnp.asarray(addr, jnp.int32)
        if addr.ndim != 2:
            raise ValueError("run_traces expects a (B, N) batch; "
                             "use addr[None] for a single trace")
        backend = resolve_backend(backend, p)
        z = jnp.zeros(addr.shape, jnp.int32)
        is_write = z if is_write is None else jnp.asarray(is_write,
                                                          jnp.int32)
        core = z if core is None else jnp.asarray(core, jnp.int32)
        tier = z if tier is None else jnp.asarray(tier, jnp.int32)
        sp.ready((addr, is_write, core, tier))
    if segment is not None:
        return _run_traces_segmented(p, addr, is_write, core, tier,
                                     segment=segment, backend=backend,
                                     chunk=chunk)
    with obs.span("sweep.program") as sp:
        if backend == "reference":
            out = _run_batch_reference(p, addr, is_write, core, tier)
        else:
            from repro.kernels import ops
            out = ops.mesi_cache_sim(addr, is_write, core, tier,
                                     params=p, chunk=chunk)
        sp.ready(out)
        if sp:
            sp.add(program="static", backend=backend,
                   row_steps=addr.shape[0] * addr.shape[1], segments=1,
                   **_state_counters(p, backend))
    return out


def _state_counters(p: cache_mod.CacheParams, backend: str) -> Dict:
    """``sweep.program``'s counters of the static program's state: one
    row's cache state in bytes, and the scoped VMEM the kernel compiled
    with (0 on the scan)."""
    limit = 0
    if backend == "pallas":
        from repro.kernels import cache_sim
        limit = cache_sim.vmem_limit_bytes(p)
    return {"state_bytes": p.state_bytes, "vmem_limit_bytes": limit}


def _pad_to_segment(x: Array, n_to: int, fill: int) -> Array:
    """Append `fill` columns so the (B, N) array spans `n_to` entries."""
    b, n = x.shape
    if n == n_to:
        return x
    return jnp.concatenate(
        [x, jnp.full((b, n_to - n), fill, jnp.int32)], axis=1)


def _run_traces_segmented(p: cache_mod.CacheParams, addr: Array,
                          is_write: Array, core: Array, tier: Array,
                          *, segment: int, backend: str,
                          chunk: int = 512
                          ) -> Tuple[Array, cache_mod.CacheState]:
    """Host loop threading the scan carry through fixed-size segments.

    One jitted device call per (B, segment) slice; only the carry (packed
    cache state + stats) persists between calls, so peak device memory is
    bounded by one segment regardless of N.  Sentinel padding rounds the
    length up to a segment multiple (padding is inert, so stats stay
    bitwise-equal to the resident program).  Both backends advance the
    identical carry (:func:`run_batch_segment`), so the streamed pallas
    kernel is bitwise-equal to the streamed — and resident — reference.
    """
    if segment < 1:
        raise ValueError(f"segment must be >= 1, got {segment}")
    b, n = addr.shape
    segment = min(segment, n)   # never pad beyond the trace itself
    n_pad = -(-n // segment) * segment
    with obs.span("sweep.program") as sp:
        addr = _pad_to_segment(addr, n_pad, SENTINEL)
        is_write = _pad_to_segment(is_write, n_pad, 0)
        core = _pad_to_segment(core, n_pad, 0)
        tier = _pad_to_segment(tier, n_pad, 0)
        carry = init_batch_carry(p, b)
        for s in range(0, n_pad, segment):
            carry = run_batch_segment(
                p, carry, addr[:, s:s + segment],
                is_write[:, s:s + segment], core[:, s:s + segment],
                tier[:, s:s + segment], donate=True, backend=backend,
                chunk=chunk)
        sp.ready(carry)
        if sp:
            sp.add(program="static", backend=backend, row_steps=b * n_pad,
                   segments=n_pad // segment, **_state_counters(p, backend))
    l1p, l2p, stats, _ = carry
    return stats, cache_mod.unpack_state(l1p, l2p)


# ---------------------------------------------------------------------------
# The §IV sweep
# ---------------------------------------------------------------------------
def build_stream_batch(spec: SweepSpec, cache: cache_mod.CacheParams,
                       chunk: int = 512,
                       routes: Optional[Sequence[
                           Optional[route_mod.RouteMap]]] = None
                       ) -> TraceBatch:
    """Materialize the (topology x workload x footprint x policy) batch.

    Each workload generates its trace **on device**
    (:meth:`~repro.workloads.base.Workload.device_trace` — pure jax ops,
    no host materialization); routes/policies only relabel each access's
    target, so the trace is generated once per (workload, footprint) and
    shared across the topology/policy cells.

    Parameters
    ----------
    spec : SweepSpec
        The grid; `spec.sim_cells` enumerates the simulated cells.
    cache : CacheParams
        Supplies `l2_bytes`, the footprint unit.
    chunk : int
        Pad granularity (Pallas chunk size).
    routes : sequence of RouteMap or None, optional
        One entry per topology-axis entry (`None` = binary tier path); the
        `tier` field of the result then carries *target ids*.  Workloads
        that emit their own per-access tier intent (``kv_decode``) route
        through :meth:`~repro.core.route.RouteMap.targets_of_tiered_lines`
        instead of the placement policy.

    Returns
    -------
    TraceBatch
        Device-resident, sentinel-padded `(B, N)` batch.
    """
    batch, _ = build_sweep_batch(spec, cache, chunk=chunk, routes=routes)
    return batch


def build_sweep_batch(spec: SweepSpec, cache: cache_mod.CacheParams,
                      chunk: int = 512,
                      routes: Optional[Sequence[
                          Optional[route_mod.RouteMap]]] = None
                      ) -> Tuple[TraceBatch, List[int]]:
    """:func:`build_stream_batch` plus the cell -> batch-row map.

    Cells whose workload owns its residency map (``wt.tier is not None``,
    e.g. ``kv_decode``) are policy-independent: they are simulated once
    per (topology, workload, footprint) and every policy cell maps to
    that single batch row — no duplicate MESI runs on bit-identical
    inputs.

    Returns
    -------
    (TraceBatch, list of int)
        The deduplicated batch, and one batch-row index per logical cell
        in ``topology-major x sim_cells`` order.
    """
    if routes is None:
        routes = [None] * len(spec.topology_axis)
    with obs.span("sweep.build") as sp:
        batch, cell_rows = _build_sweep_batch(spec, cache, chunk, routes)
        sp.ready(vars(batch))
        _count_batch(sp, batch)
    return batch, cell_rows


def _count_batch(sp, batch: TraceBatch) -> None:
    """The ``sweep.build`` counters of a stacked batch."""
    if sp:
        sp.add(rows=batch.batch, steps=batch.length,
               accesses=batch.total_accesses)


def _device_traces(spec: SweepSpec, cache: cache_mod.CacheParams) -> Dict:
    """Each (workload, footprint) of the grid's trace, generated once."""
    cell_traces = {}
    for wl, k, _ in spec.sim_cells:
        if (wl, k) not in cell_traces:
            with obs.span("sweep.build.trace") as sp:
                wt = wl.device_trace(k * cache.l2_bytes)
                sp.ready(vars(wt))
                if sp:
                    sp.add(workload=wl.name,
                           accesses=int(wt.addr.shape[0]))
            cell_traces[(wl, k)] = wt
    return cell_traces


def _build_sweep_batch(spec, cache, chunk, routes):
    """The body of :func:`build_sweep_batch`."""
    cell_traces = _device_traces(spec, cache)
    traces: List[Tuple] = []
    row_of = {}
    cell_rows: List[int] = []
    for ti, route in enumerate(routes):
        for wl, k, pol in spec.sim_cells:
            wt = cell_traces[(wl, k)]
            key = ((ti, wl, k) if wt.tier is not None
                   else (ti, wl, k, pol))
            if key not in row_of:
                if wt.tier is not None:    # workload-owned residency map
                    tier = (wt.tier if route is None
                            else route.targets_of_tiered_lines(wt.tier,
                                                               wt.addr))
                elif route is None:
                    tier = numa_mod.tier_of_lines(pol, wt.addr, wt.n_pages)
                else:
                    tier = route.target_of_lines(pol, wt.addr, wt.n_pages)
                traces.append((wt.addr, wt.is_write, None, tier))
                row_of[key] = len(traces) - 1
            cell_rows.append(row_of[key])
    return stack_device_traces(traces, pad_to_multiple=chunk), cell_rows


def _narrow_idx(t_max: int, t_route: int) -> List[int]:
    """Stat columns a `t_route`-target route occupies in a `t_max`-wide
    layout (the complement is identically zero — see `_narrow_stats`)."""
    return (list(range(4)) + list(range(4, 4 + t_route))
            + list(range(4 + t_max, 4 + t_max + t_route))
            + list(range(4 + 2 * t_max, 8 + 2 * t_max)))


def _narrow_stats(stats: np.ndarray, t_max: int, t_route: int) -> np.ndarray:
    """Drop the (all-zero) per-target columns a narrower route never hit.

    The batched program sizes every row's stats for the widest topology
    (`t_max` targets); a route with `t_route < t_max` targets only ever
    routed ids `< t_route`, so the dropped read/write columns are zero.
    """
    if t_route == t_max:
        return stats
    return stats[:, _narrow_idx(t_max, t_route)]


class LocalExecutor:
    """Default sweep executor: the whole batch as ONE resident program.

    The executor seam is what :mod:`repro.core.distribute` plugs into —
    it owns only the raw device execution of an already-built batch
    (grid flattening, routing, timing and row assembly stay in this
    module), so any executor that returns the same counters produces
    bit-identical sweep rows.
    """

    def run_static(self, p: cache_mod.CacheParams, batch: TraceBatch,
                   *, backend: str, chunk: int) -> np.ndarray:
        """Simulate the stacked batch; return host (B, nstats) int64."""
        stats, _ = run_traces(p, batch.addr, batch.is_write, core=None,
                              tier=batch.tier, backend=backend, chunk=chunk)
        return np.asarray(jax.block_until_ready(stats), np.int64)

    def run_dynamic(self, p: cache_mod.CacheParams, tb: "TieringBatch",
                    *, slot_len: int, k_max: int,
                    backend: str = "reference"):
        """Run the epoch-structured batch; return `DynOutputs`."""
        return tiering_dyn.run_dynamic(
            p, tb.batch.addr, tb.batch.is_write, tb.batch.core,
            tb.batch.tier, slot_len=slot_len, k_max=k_max,
            dyn_flag=tb.dyn_flag, page_map0=tb.page_map0,
            n_pages=tb.n_pages, budget=tb.budget, threshold=tb.threshold,
            period=tb.period, dram_cap=tb.dram_cap,
            page_target_lines=tb.page_target_lines,
            ssd_tid=tb.ssd_tid, cxl_cap=tb.cxl_cap,
            s_warm=tb.s_warm, s_meas=tb.s_meas, s_per=tb.s_per,
            backend=backend)


_LOCAL_EXECUTOR = LocalExecutor()


def _resolve_executor(executor, resume, fault_plan, report):
    """The executor the resilience knobs select (None = LocalExecutor).

    ``resume`` / ``fault_plan`` / ``report`` build a
    :class:`repro.core.distribute.ResilientExecutor` (deferred import —
    distribute sits above engine); they are mutually exclusive with an
    explicit ``executor``, which owns its own configuration.
    """
    if resume is None and fault_plan is None and report is None:
        return executor
    if executor is not None:
        raise ValueError(
            "pass either executor= or the resilience knobs "
            "(resume/fault_plan/report), not both — configure a "
            "ResilientExecutor directly for full control")
    from repro.core import distribute
    return distribute.ResilientExecutor(checkpoint=resume,
                                        fault_plan=fault_plan,
                                        report=report)


def run_sweep(spec: SweepSpec, cache: cache_mod.CacheParams,
              timing: TimingConfig, *, chunk: int = 512,
              executor=None, resume=None, fault_plan=None,
              report=None) -> List[Dict]:
    """Run the whole characterization suite as one batched device program.

    Parameters
    ----------
    spec : SweepSpec
        The (workload x topology x footprint x policy x cpu) grid.
    cache : CacheParams
        Cache geometry (stats width is adjusted to the widest route).
    timing : TimingConfig
        Per-tier timing model closing the Picard fixed point.
    chunk : int
        Trace pad/stream granularity.
    executor : optional
        Execution strategy for the stacked batch (`run_static` /
        `run_dynamic` duck type).  Default: :class:`LocalExecutor`, one
        resident device program; :class:`repro.core.distribute.
        ShardedExecutor` shards rows across devices and/or streams trace
        segments.  Any executor must return bitwise-identical counters,
        so rows never depend on the execution strategy (test-enforced).
    resume : CheckpointPolicy, path, or None
        Run (or resume) through a :class:`repro.core.distribute.
        ResilientExecutor` checkpointing to this directory: a sweep
        killed at an arbitrary segment boundary and rerun with the same
        ``resume=`` fast-forwards past the completed segments/shards
        and yields bitwise-identical rows (test- and golden-enforced).
    fault_plan : repro.core.resilience.FaultPlan, optional
        Deterministic failure injection (selects the resilient
        executor, like ``resume``).
    report : repro.core.resilience.RunReport, optional
        Event sink recording retries, resumes, degradations and
        checkpoint timings.

    Returns
    -------
    list of dict
        One row per (topology, workload, footprint, policy, cpu) — the
        same schema as `CXLRAMSim.stream_suite` rows, plus the raw
        `stats` counters, a `workload` label, a `topology` label when the
        spec sweeps topologies, and per-target `bw_cxl{k}_gbps` /
        `lat_cxl{k}_ns` columns on multi-target rows.  Stats are
        bitwise-equal to running each configuration through the
        sequential per-config path.
    """
    from repro.workloads.base import Stream  # deferred: wl builds on core
    results = sweep_results(spec, cache, timing, chunk=chunk,
                            executor=executor, resume=resume,
                            fault_plan=fault_plan, report=report)
    rows: List[Dict] = []
    i = 0
    for dist in spec.distributions_axis:
        for sp in spec.sampling_axis:
            for tr in spec.tiering_axis:
                for topo in spec.topology_axis:
                    for wl, k, pol in spec.sim_cells:
                        for _cpu in spec.cpus:
                            r = results[i]
                            row = {"workload": wl.name,
                                   "footprint_x_l2": k,
                                   "policy": numa_mod.describe(pol),
                                   "cpu": r.cpu, **r.row(),
                                   "stats": r.stats}
                            if isinstance(wl, Stream):  # STREAM only
                                row["kernel"] = wl.kernel
                            if topo is not None:
                                row["topology"] = topo.name
                            if spec.tiering:
                                row["tiering"] = tiering_dyn.describe(tr)
                            if spec.sampling:
                                row["sampling"] = sampling_mod.describe(sp)
                            if spec.distributions:
                                row["distribution"] = (
                                    "off" if dist is None else dist.label)
                            rows.append(row)
                            i += 1
    return rows


def sweep_results(spec: SweepSpec, cache: cache_mod.CacheParams,
                  timing: TimingConfig, *, chunk: int = 512,
                  executor=None, resume=None, fault_plan=None,
                  report=None) -> List[RunResult]:
    """`run_sweep` returning full RunResults (row order identical).

    One device call simulates every (topology, workload, footprint,
    policy) cell — topologies with different target counts share the
    program by padding the stats width to the widest route (unused
    per-target counters stay zero and are dropped again before timing).
    Each cell's stats are then broadcast across the CPU-model axis (CPU
    models never touch cache state) and the Picard timing fixed point
    closes vectorized per topology group, with each group's own route
    (switch coupling included).  Workloads with serial dependences
    (pointer chase) collapse each CPU model's memory-level parallelism to
    1 via :meth:`~repro.workloads.base.Workload.cpu_for` — dependent
    loads cannot overlap.

    Parameters
    ----------
    spec, cache, timing, chunk, resume, fault_plan, report
        As in :func:`run_sweep`.

    Returns
    -------
    list of RunResult
        One per grid row, ordered tiering-major, then topology,
        workload, footprint, policy, cpu.
    """
    with obs.span("sweep") as sweep_span:
        epoch = (any(tr is not None for tr in spec.tiering_axis)
                 or any(sp is not None for sp in spec.sampling_axis))
        executor = _resolve_executor(executor, resume, fault_plan, report)
        executor = executor if executor is not None else _LOCAL_EXECUTOR
        routes = [None if tp is None else route_mod.build_route(tp, timing)
                  for tp in spec.topology_axis]
        if epoch:
            out = _sweep_results_dynamic(spec, cache, timing, routes,
                                         executor=executor)
        else:
            out = _sweep_results_static(
                spec, cache, timing, routes,
                backend=resolve_backend(spec.backend, cache), chunk=chunk,
                executor=executor)
        if sweep_span:
            sweep_span.add(rows=len(out))
    return out


def _sweep_results_static(spec: SweepSpec, cache: cache_mod.CacheParams,
                          timing: TimingConfig,
                          routes: Sequence[Optional[route_mod.RouteMap]],
                          *, backend: str, chunk: int, executor
                          ) -> List[RunResult]:
    """The static-program body of `sweep_results`."""
    t_max = max(2 if r is None else r.n_targets for r in routes)
    p = dataclasses.replace(cache, n_targets=t_max)
    batch, cell_rows = build_sweep_batch(spec, cache, chunk=chunk,
                                         routes=routes)
    stats = executor.run_static(p, batch, backend=backend, chunk=chunk)
    cells = spec.sim_cells
    n_cells = len(cells)
    rows_cpus = [wl.cpu_for(cpu) for wl, _k, _pol in cells
                 for cpu in spec.cpus]
    out: List[RunResult] = []
    # the distributions axis only re-closes the timing fixed point — the
    # device program above ran once for every entry
    for dist in spec.distributions_axis:
        results: List[RunResult] = []
        for ti, route in enumerate(routes):
            # gather this topology's cells (policy-duplicate cells
            # share rows)
            block = stats[cell_rows[ti * n_cells:(ti + 1) * n_cells]]
            t_route = 2 if route is None else route.n_targets
            block = _narrow_stats(block, t_max, t_route)
            rows_stats = np.repeat(block, len(spec.cpus), axis=0)
            results.extend(time_batch(timing, rows_cpus, rows_stats,
                                      route=route, dist=dist))
        # explicit all-None tiering/sampling axes repeat the static
        # block per entry — independent copies, so no rows share
        # mutable state
        out.extend(results)
        n_copies = len(spec.sampling_axis) * len(spec.tiering_axis)
        for _ in range(n_copies - 1):
            out.extend(_copy_result(r) for r in results)
    return out


def _copy_result(r: RunResult) -> RunResult:
    """Independent copy of a RunResult (no shared mutable containers)."""
    return dataclasses.replace(
        r, stats=dict(r.stats), miss_rates=dict(r.miss_rates),
        achieved_gbps=dict(r.achieved_gbps),
        loaded_latency_ns=dict(r.loaded_latency_ns),
        lat_percentiles=(None if r.lat_percentiles is None else
                         {k: dict(v) for k, v in r.lat_percentiles.items()}))


# ---------------------------------------------------------------------------
# Dynamic tiering: the epoch-structured sweep path
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TieringBatch:
    """Per-row inputs of the epoch program (see `tiering_dyn.run_dynamic`).

    `batch.tier` carries the per-line CXL decode target for dynamic rows
    and the final per-access target for static (`tiering=None`) rows —
    `dyn_flag` selects which interpretation each row uses on device.
    """
    batch: TraceBatch
    dyn_flag: np.ndarray            # (B,)  1 = page map routes, 0 = static
    page_map0: Array                # (B, P) initial page -> {0, 1[, 2]}
    n_pages: np.ndarray             # (B,)
    budget: np.ndarray              # (B,)
    threshold: np.ndarray           # (B,)
    period: np.ndarray              # (B,) slots per epoch
    dram_cap: np.ndarray            # (B,)
    ssd_tid: np.ndarray             # (B,) SSD target id; 0 = two-tier row
    cxl_cap: np.ndarray             # (B,) level-1 capacity (pages)
    page_target_lines: Array        # (B, P, T)
    s_warm: np.ndarray              # (B,) sampling warm slots (scan units)
    s_meas: np.ndarray              # (B,) sampling measure slots
    s_per: np.ndarray               # (B,) sampling period; 0 = exact
    cell_rows: List[int]            # logical cell -> batch row


_UNBOUNDED_PAGES = 1 << 30          # "no DRAM capacity pressure" sentinel


def build_tiering_batch(spec: SweepSpec, cache: cache_mod.CacheParams,
                        routes: Sequence[Optional[route_mod.RouteMap]],
                        slot: int, t_max: int) -> TieringBatch:
    """Materialize the (sampling x tiering x topology x workload x
    footprint x policy) batch for the epoch program.

    Row dedup mirrors :func:`build_sweep_batch`: cells whose workload
    owns its residency map are policy-independent (dynamic rows seed the
    tierer with the first-touch page map of the workload's own tier
    stream — :func:`repro.core.numa.first_touch_page_map`); every
    ``tiering=None`` cell shares one row across all ``None`` entries,
    and likewise every ``sampling=None`` cell across ``None`` sampling
    entries (sampled cells never share rows with exact ones — their
    device stats are masked differently).

    Parameters
    ----------
    spec, cache
        The grid (``spec.tiering_axis`` supplies the tiering entries).
    routes : sequence of RouteMap or None
        One per topology-axis entry.
    slot : int
        Epoch-scan granularity (gcd of the dynamic epoch lengths); the
        stacked traces are sentinel-padded to a multiple of it.
    t_max : int
        Stats width (widest route).

    Returns
    -------
    TieringBatch
    """
    with obs.span("sweep.build") as sp:
        tb = _build_tiering_batch(spec, cache, routes, slot, t_max)
        sp.ready((vars(tb), vars(tb.batch)))
        _count_batch(sp, tb.batch)
    return tb


def _build_tiering_batch(spec, cache, routes, slot, t_max):
    """The body of :func:`build_tiering_batch`."""
    cells = spec.sim_cells
    cell_traces = _device_traces(spec, cache)
    p_max = max(wt.n_pages for wt in cell_traces.values())
    ptl_of = []
    for route in routes:
        if route is None:
            ptl = jnp.zeros((p_max, t_max), jnp.int32) \
                .at[:, 1].set(numa_mod.LINES_PER_PAGE)
        else:
            ptl = route.page_target_lines(p_max, width=t_max)
        ptl_of.append(ptl)

    traces: List[Tuple] = []
    pmap0s: List[Array] = []
    scalars: List[Tuple[int, ...]] = []
    row_of: Dict = {}
    cell_rows: List[int] = []
    for si, sp in enumerate(spec.sampling_axis):
        skey = si if sp is not None else -1  # exact entries share rows
        sw, sm, spr = sampling_mod.scan_scalars(sp, slot)
        for tri, tr in enumerate(spec.tiering_axis):
            dynamic = tr is not None
            tkey = tri if dynamic else -1  # all static entries share rows
            for ti, route in enumerate(routes):
                for wl, k, pol in cells:
                    wt = cell_traces[(wl, k)]
                    key = ((skey, tkey, ti, wl, k)
                           if wt.tier is not None
                           else (skey, tkey, ti, wl, k, pol))
                    if key not in row_of:
                        if dynamic:
                            tier = (jnp.ones_like(wt.addr)
                                    if route is None
                                    else route.cxl_targets_of_lines(
                                        wt.addr))
                            if wt.tier is not None:
                                pmap0 = numa_mod.first_touch_page_map(
                                    wt.tier, wt.addr, wt.n_pages)
                            else:
                                pmap0 = (pol.tiers(wt.n_pages) != 0) \
                                    .astype(jnp.int32)
                            cap = (tr.dram_capacity_pages
                                   if tr.dram_capacity_pages is not None
                                   else _UNBOUNDED_PAGES)
                            ssd_t = (0 if route is None
                                     else route.ssd_tid)
                            l1cap = (tr.cxl_capacity_pages
                                     if tr.cxl_capacity_pages is not None
                                     else _UNBOUNDED_PAGES)
                            sc = (1, wt.n_pages, tr.budget, tr.threshold,
                                  tr.epoch_len // slot, cap, ssd_t,
                                  l1cap)
                        else:
                            # static rows: precomputed final targets,
                            # exactly the legacy build_sweep_batch math
                            if wt.tier is not None:
                                tier = (wt.tier if route is None
                                        else route.targets_of_tiered_lines(
                                            wt.tier, wt.addr))
                            elif route is None:
                                tier = numa_mod.tier_of_lines(
                                    pol, wt.addr, wt.n_pages)
                            else:
                                tier = route.target_of_lines(
                                    pol, wt.addr, wt.n_pages)
                            pmap0 = jnp.ones((wt.n_pages,), jnp.int32)
                            sc = (0, wt.n_pages, 0, 1, 1,
                                  _UNBOUNDED_PAGES, 0, _UNBOUNDED_PAGES)
                        if wt.n_pages < p_max:  # pad: CXL, never eligible
                            pmap0 = jnp.concatenate([
                                jnp.asarray(pmap0, jnp.int32),
                                jnp.ones((p_max - wt.n_pages,),
                                         jnp.int32)])
                        traces.append((wt.addr, wt.is_write, None, tier))
                        pmap0s.append(jnp.asarray(pmap0, jnp.int32))
                        scalars.append(sc + (sw, sm, spr, ti))
                        row_of[key] = len(traces) - 1
                    cell_rows.append(row_of[key])
    batch = stack_device_traces(traces, pad_to_multiple=slot)
    sc = np.asarray(scalars, np.int64)
    return TieringBatch(
        batch=batch, dyn_flag=sc[:, 0], page_map0=jnp.stack(pmap0s),
        n_pages=sc[:, 1], budget=sc[:, 2], threshold=sc[:, 3],
        period=sc[:, 4], dram_cap=sc[:, 5], ssd_tid=sc[:, 6],
        cxl_cap=sc[:, 7],
        page_target_lines=jnp.stack([ptl_of[ti] for ti in sc[:, 11]]),
        s_warm=sc[:, 8], s_meas=sc[:, 9], s_per=sc[:, 10],
        cell_rows=cell_rows)


def _sweep_results_dynamic(spec: SweepSpec, cache: cache_mod.CacheParams,
                           timing: TimingConfig,
                           routes: Sequence[Optional[route_mod.RouteMap]],
                           *, executor) -> List[RunResult]:
    """The epoch-structured twin of the static `sweep_results` body.

    One `tiering_dyn.run_dynamic` device call simulates every
    (sampling, tiering, topology, workload, footprint, policy) cell —
    static (``tiering=None``) rows ride the same vmapped program with a
    zero migration budget and their precomputed targets, so their stats
    stay bitwise-equal to the legacy path (test-enforced).  Migration
    line counts feed `time_batch(mig_lines=...)`; dynamic rows
    additionally get `migrated_pages` and per-epoch DRAM hit-tier
    fractions.  Sampled rows (``sampling != None``) replace the masked
    device counters with whole-trace estimates
    (:func:`repro.core.sampling.estimate` over the per-slot snapshot
    deltas) before the timing fixed point and carry per-counter 95%
    confidence intervals.
    """
    t_max = max(2 if r is None else r.n_targets for r in routes)
    p = dataclasses.replace(cache, n_targets=t_max)
    dyn = [tr for tr in spec.tiering_axis if tr is not None]
    sampled = [sp for sp in spec.sampling_axis if sp is not None]
    if dyn:
        # sampling slots must nest inside epoch slots: scan at the gcd
        # (a pure-dynamic sweep keeps its legacy granularity untouched)
        slot = tiering_dyn.slot_length(dyn)
        if sampled:
            slot = math.gcd(slot, sampling_mod.SLOT_LEN)
        k_max = max(1, max(tr.budget for tr in dyn))
    else:
        slot = sampling_mod.SLOT_LEN
        k_max = 1
    for tr in dyn:
        if tr.epoch_len % slot:
            raise ValueError(
                f"epoch_len {tr.epoch_len} is not a multiple of the "
                f"sweep's epoch gcd {slot}")
    tb = build_tiering_batch(spec, cache, routes, slot, t_max)
    backend = resolve_backend(spec.backend, p, epoch=True,
                              n_pages=tb.page_map0.shape[1])
    out = executor.run_dynamic(p, tb, slot_len=slot, k_max=k_max,
                               backend=backend)
    stats = np.asarray(jax.block_until_ready(out.stats), np.int64)
    mig = np.stack([np.asarray(out.mig_read, np.int64),
                    np.asarray(out.mig_write, np.int64)], axis=1)
    slots = np.asarray(out.slots, np.int64)          # (B, E, 4)
    snaps = np.asarray(out.snapshots)                # (B, E, nstats)
    meas = np.asarray(out.meas)                      # (B, E)
    cells = spec.sim_cells
    n_cells = len(cells)
    n_cpus = len(spec.cpus)
    n_tier = len(spec.tiering_axis)
    rows_cpus = [wl.cpu_for(cpu) for wl, _k, _pol in cells
                 for cpu in spec.cpus]

    # whole-trace estimates per sampled batch row (dedup-shared cells
    # compute once; a batch row belongs to exactly one sampling entry)
    est_of: Dict[int, sampling_mod.Estimate] = {}

    def _est(br: int, sp: sampling_mod.SamplingSpec):
        if br not in est_of:
            est_of[br] = sampling_mod.estimate(
                cache_mod.snapshot_deltas(snaps[br]), slots[br, :, 0],
                meas[br], confidence=sp.confidence)
        return est_of[br]

    results: List[RunResult] = []
    # the distributions axis only re-closes the timing fixed point —
    # the epoch-structured device program above ran once
    for dist in spec.distributions_axis:
        for si, sp in enumerate(spec.sampling_axis):
            for tri, tr in enumerate(spec.tiering_axis):
                for ti, route in enumerate(routes):
                    base = (((si * n_tier + tri) * len(routes) + ti)
                            * n_cells)
                    block_rows = tb.cell_rows[base:base + n_cells]
                    t_route = 2 if route is None else route.n_targets
                    if sp is None:
                        block = stats[block_rows]
                        ests = None
                    else:
                        ests = [_est(br, sp) for br in block_rows]
                        block = np.stack([e.stats for e in ests])
                    block = _narrow_stats(block, t_max, t_route)
                    mig_block = mig[block_rows][:, :, :t_route]
                    rows_stats = np.repeat(block, n_cpus, axis=0)
                    rows_mig = np.repeat(mig_block, n_cpus, axis=0)
                    res = time_batch(timing, rows_cpus, rows_stats,
                                     route=route, mig_lines=rows_mig,
                                     dist=dist)
                    if tr is not None:
                        period = tr.epoch_len // slot
                        for j, r in enumerate(res):
                            br = block_rows[j // n_cpus]
                            r.migrated_pages = int(
                                slots[br, :, 2].sum()
                                + slots[br, :, 3].sum())
                            r.epoch_dram_frac = \
                                tiering_dyn.epoch_fractions(
                                    slots[br], period)
                    if ests is not None:
                        nidx = _narrow_idx(t_max, t_route)
                        names = cache_mod.stat_names(t_route)
                        for j, r in enumerate(res):
                            e = ests[j // n_cpus]
                            r.sampled_frac = e.sampled_frac
                            r.sample_windows = e.n_windows
                            r.stats_ci95 = {
                                nm: float(e.ci[ci]) for nm, ci
                                in zip(names, nidx)}
                            r.l2_miss_rate_ci95 = e.l2_miss_rate_ci()[1]
                    results.extend(res)
    return results

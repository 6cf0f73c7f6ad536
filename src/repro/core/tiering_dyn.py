"""Epoch-based dynamic tiering: TPP-style hot-page promotion / demotion.

The paper characterizes *static* page placement (zNUMA bind, flat-mode
first touch, weighted interleave — :mod:`repro.core.numa`).  Real
deployments run a dynamic tierer: the kernel samples per-page access
counts over an epoch, migrates hot pages CXL→DRAM and, under DRAM
capacity pressure, demotes cold pages DRAM→CXL (Linux NUMA balancing /
TPP).  This module is that policy dimension for the batched trace
engine (:mod:`repro.core.engine`):

  * the stacked trace is split into fixed-length **epochs** inside the
    existing scan (an outer ``lax.scan`` over epoch slots, the inner
    scan the exact packed MESI step of :mod:`repro.core.cache`);
  * per epoch, per-page access counters accumulate on device;
  * at each epoch boundary the **promotion/demotion rule** runs: the
    top-k hottest CXL pages (access count >= ``threshold``) promote to
    DRAM and, when DRAM capacity is exhausted, the coldest DRAM pages
    demote to make room — both bounded by the per-epoch migration
    ``budget``;
  * the page→tier map is **scan state**: the rewritten map routes the
    next epoch's accesses (CXL-destined lines still decode through the
    committed HDM programs via the precomputed per-line CXL target);
  * migration traffic (page-sized reads on the source + writes on the
    destination endpoint) is accumulated per target and charged into
    :func:`repro.core.machine.time_batch`'s Picard fixed point, so
    bandwidth contention from migration is first-class.

Determinism and the host twin
-----------------------------
Promotion/demotion candidates are ranked through an injective integer
key (:func:`encode_hot_key`): ``count * n_pages + (n_pages - 1 - page)``
— higher count wins, ties break toward the lower page index, and no two
pages ever share a key, so ``lax.top_k`` selection is bitwise
deterministic.  :func:`host_simulate` replays the identical epoch loop
in NumPy (the migration decisions depend only on the trace and the map
evolution, never on cache state), yielding the per-access target
sequence, per-epoch counters, migration totals and the final page map —
the parity oracle ``tests/test_tiering_dyn.py`` holds the device
program to, with the same contract as the workload generators'
``host_trace`` (:mod:`repro.workloads.base`).

Static rows ride along: a row with ``budget == 0`` (or with its
precomputed per-access targets flagged as an override) never migrates
and its stats are bitwise-equal to the legacy static path — which is
how ``SweepSpec.tiering`` mixes ``None`` and dynamic entries in ONE
vmapped device program (test-enforced).

Three tiers (DRAM → CXL-DRAM → CXL-SSD)
---------------------------------------
On a route with a flash-backed target (``RouteMap.ssd_tid > 0``) the
page map becomes three-level — ``{0 DRAM, 1 CXL-DRAM, 2 CXL-SSD}`` —
and each epoch boundary runs a second migration stage after the
classic DRAM↔CXL one: hot level-2 pages (count >= ``threshold``)
promote SSD→CXL (budget-bounded), then any level-1 population beyond
``cxl_capacity_pages`` demotes its coldest pages CXL→SSD.  SSD→CXL
promotion reads the page from the SSD target and writes its CXL
endpoints; CXL→SSD demotion reads the endpoints and writes the SSD —
all charged into the timing fixed point like every other migration.
Rows without an SSD target (``ssd_tid == 0``) take the identical code
path with the stage gated off, so legacy two-tier programs stay
bitwise-unchanged (test-enforced).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_mod
from repro.core import obs
from repro.core.numa import LINES_PER_PAGE

Array = jax.Array

SENTINEL = cache_mod.SENTINEL

#: Column order of the per-slot counters returned by :func:`run_dynamic`
#: (``slots[..., i]``) and :func:`host_simulate` (``HostResult.slots``).
#: On three-tier rows, SSD-stage migrations fold into ``promoted`` /
#: ``demoted`` (SSD→CXL counts as a promotion, CXL→SSD as a demotion).
SLOT_FIELDS = ("acc_total", "acc_dram", "promoted", "demoted")

#: "No capacity bound" sentinel for page-count scalars (fits int32).
UNBOUNDED_PAGES = 1 << 30


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DynamicTiering:
    """One dynamic-tiering policy point (an entry of ``SweepSpec.tiering``).

    Parameters
    ----------
    epoch_len : int
        Accesses per epoch (the kernel's scan interval).  Within one
        sweep every dynamic entry's ``epoch_len`` must be a multiple of
        the gcd of all entries — the engine scans at that granularity
        and fires each row's migration step on its own boundaries.
    budget : int
        Maximum pages *promoted* per epoch (demotions are bounded by the
        same budget).  ``0`` never migrates — bitwise-equal to static
        placement.
    threshold : int
        Minimum access count for a CXL page to be promotion-eligible.
        Must be >= 1 so epochs made entirely of sentinel padding can
        never migrate (sentinel-padding invariance, test-enforced).
    dram_capacity_pages : int, optional
        DRAM pages available to this footprint; promotions beyond the
        free capacity force an equal number of cold-page demotions.
        ``None`` = unbounded (DRAM dwarfs the footprint).  Derive it
        from the shared :class:`repro.memory.tiering.TierSpec` via
        :func:`repro.memory.tiering.dynamic_tiering`.
    cxl_capacity_pages : int, optional
        CXL-DRAM (level-1) pages available before cold pages spill to
        the CXL-SSD tier — only meaningful on a route with an SSD
        target (``RouteMap.ssd_tid > 0``), ignored otherwise.  ``None``
        = unbounded (nothing ever demotes to flash).
    """
    epoch_len: int = 4096
    budget: int = 8
    threshold: int = 1
    dram_capacity_pages: Optional[int] = None
    cxl_capacity_pages: Optional[int] = None

    def __post_init__(self) -> None:
        if self.epoch_len < 1:
            raise ValueError(f"epoch_len must be >= 1, got {self.epoch_len}")
        if self.budget < 0:
            raise ValueError(f"budget must be >= 0, got {self.budget}")
        if self.threshold < 1:
            raise ValueError("threshold must be >= 1 (a zero threshold "
                             "would let all-sentinel pad epochs migrate)")

    @property
    def label(self) -> str:
        cap = ("" if self.dram_capacity_pages is None
               else f",cap={self.dram_capacity_pages}")
        l1 = ("" if self.cxl_capacity_pages is None
              else f",l1cap={self.cxl_capacity_pages}")
        return (f"tpp(e={self.epoch_len},k={self.budget},"
                f"t={self.threshold}{cap}{l1})")


def describe(tiering: Optional[DynamicTiering]) -> str:
    """Row label for the ``tiering`` sweep axis (``'static'`` for None)."""
    return "static" if tiering is None else tiering.label


def slot_length(tierings: Sequence[Optional[DynamicTiering]]) -> int:
    """Scan granularity: gcd of every dynamic entry's ``epoch_len``."""
    lens = [t.epoch_len for t in tierings if t is not None]
    if not lens:
        raise ValueError("no dynamic tiering entries")
    return functools.reduce(math.gcd, lens)


# ---------------------------------------------------------------------------
# The ranking key (promotion/demotion candidate order)
# ---------------------------------------------------------------------------
def encode_hot_key(count, page, n_pages: int, xp=jnp):
    """Injective hotness key: higher count wins, ties -> lower page index.

    ``key = count * n_pages + (n_pages - 1 - page)``.  Because the page
    index is folded in, no two pages share a key, so top-k selection has
    no ties to break — the device (``lax.top_k``) and host
    (``np.argsort``) orders are identical by construction.

    Parameters
    ----------
    count : array of int32
        Per-page access counts (this epoch).
    page : array of int32
        Page indices in ``[0, n_pages)``.
    n_pages : int
        Key stride; callers guard ``max_count * n_pages`` against int32
        overflow (:func:`run_dynamic` raises).
    xp : module
        ``numpy`` or ``jax.numpy``.
    """
    count = xp.asarray(count, xp.int32)
    page = xp.asarray(page, xp.int32)
    return count * xp.int32(n_pages) + (xp.int32(n_pages - 1) - page)


def decode_hot_key(key, n_pages: int, xp=jnp):
    """Inverse of :func:`encode_hot_key` -> ``(count, page)``."""
    key = xp.asarray(key, xp.int32)
    count = key // xp.int32(n_pages)
    page = xp.int32(n_pages - 1) - key % xp.int32(n_pages)
    return count, page


# ---------------------------------------------------------------------------
# Device program
# ---------------------------------------------------------------------------
class DynOutputs(NamedTuple):
    """Per-row outputs of :func:`run_dynamic` (leading batch axis B)."""
    stats: Array      # (B, nstats(T)) final cache/tier counters
    page_map: Array   # (B, P) final page -> {0 DRAM, 1 CXL[, 2 SSD]} intent
    mig_read: Array   # (B, T) migration lines read per target
    mig_write: Array  # (B, T) migration lines written per target
    slots: Array      # (B, E, 4) per-slot counters, see SLOT_FIELDS
    snapshots: Array  # (B, E, nstats(T)) cumulative stats after each slot
    meas: Array       # (B, E) 0/1 per-slot measurement flag (sampling)


def _migration_step(pmap, counts, ptl, page_ids, pvalid, rank,
                    budget, threshold, dram_cap, do_mig, cmax,
                    n_pages_key: int, k_max: int):
    """One epoch-boundary promotion/demotion decision (pure, vectorized).

    Returns ``(new_pmap, pro_lines, dem_lines, n_pro, n_dem)`` — all
    already gated by ``do_mig`` (no-ops otherwise).

    Only level-1 (CXL-DRAM) pages are promotion candidates — on a
    two-tier map ``pmap == 1`` and the historical ``pmap != 0`` select
    the same set, and level-2 (SSD) pages have their own stage
    (:func:`_ssd_stage`).
    """
    is_cxl = (pmap == 1) & pvalid
    is_dram = (pmap == 0) & pvalid
    hot = is_cxl & (counts >= threshold)
    pkey = jnp.where(hot, encode_hot_key(counts, page_ids, n_pages_key),
                     jnp.int32(-1))
    pvals, pidx = jax.lax.top_k(pkey, k_max)
    # coldness key: invert the count (cmax bounds any epoch's count)
    dkey = jnp.where(is_dram,
                     encode_hot_key(cmax - counts, page_ids, n_pages_key),
                     jnp.int32(-1))
    dvals, didx = jax.lax.top_k(dkey, k_max)

    n_want = ((pvals >= 0) & (rank < budget)).sum().astype(jnp.int32)
    free = jnp.maximum(dram_cap - is_dram.sum().astype(jnp.int32), 0)
    n_dem_needed = jnp.clip(n_want - free, 0, budget)
    dmask = (dvals >= 0) & (rank < n_dem_needed) & do_mig
    n_dem = dmask.sum().astype(jnp.int32)
    pmask = ((pvals >= 0) & (rank < jnp.minimum(budget, free + n_dem))
             & do_mig)
    n_pro = pmask.sum().astype(jnp.int32)

    # promoted (CXL) and demoted (DRAM) page sets are disjoint by
    # construction, so the two scatters commute
    new_pmap = pmap.at[pidx].set(jnp.where(pmask, 0, pmap[pidx]))
    new_pmap = new_pmap.at[didx].set(jnp.where(dmask, 1, new_pmap[didx]))
    pro_lines = (ptl[pidx] * pmask[:, None]).sum(axis=0)  # (T,) from CXL
    dem_lines = (ptl[didx] * dmask[:, None]).sum(axis=0)  # (T,) to CXL
    return new_pmap, pro_lines, dem_lines, n_pro, n_dem


def _ssd_stage(pmap, counts, ptl, page_ids, pvalid, rank,
               budget, threshold, cxl_cap, do_ssd, cmax,
               n_pages_key: int, k_max: int):
    """The three-tier second stage: SSD↔CXL-DRAM traffic at a boundary.

    Runs after :func:`_migration_step` on its rewritten map.  Hot
    level-2 pages (count >= ``threshold``) promote SSD→CXL, bounded by
    ``budget``; then any level-1 population beyond ``cxl_cap`` demotes
    its coldest pages CXL→SSD (also budget-bounded).  ``do_ssd`` gates
    the whole stage — rows without an SSD target run the identical
    arithmetic with every mask false, leaving the map and the migration
    totals bitwise-untouched.

    Returns ``(new_pmap, sup_lines, over_lines, n_sup, n_over)`` with
    ``sup_lines``/``over_lines`` the CXL-endpoint line attribution of
    the promoted/demoted pages (the SSD side is ``n * LINES_PER_PAGE``
    at the SSD target, charged by the caller).
    """
    hot2 = (pmap == 2) & pvalid & (counts >= threshold)
    skey = jnp.where(hot2, encode_hot_key(counts, page_ids, n_pages_key),
                     jnp.int32(-1))
    svals, sidx = jax.lax.top_k(skey, k_max)
    smask = (svals >= 0) & (rank < budget) & do_ssd
    n_sup = smask.sum().astype(jnp.int32)
    new_pmap = pmap.at[sidx].set(jnp.where(smask, 1, pmap[sidx]))

    is_l1 = (new_pmap == 1) & pvalid
    over = jnp.clip(is_l1.sum().astype(jnp.int32) - cxl_cap, 0, budget)
    okey = jnp.where(is_l1,
                     encode_hot_key(cmax - counts, page_ids, n_pages_key),
                     jnp.int32(-1))
    ovals, oidx = jax.lax.top_k(okey, k_max)
    omask = (ovals >= 0) & (rank < over) & do_ssd
    n_over = omask.sum().astype(jnp.int32)
    new_pmap = new_pmap.at[oidx].set(jnp.where(omask, 2, new_pmap[oidx]))
    sup_lines = (ptl[sidx] * smask[:, None]).sum(axis=0)   # (T,) to CXL
    over_lines = (ptl[oidx] * omask[:, None]).sum(axis=0)  # (T,) from CXL
    return new_pmap, sup_lines, over_lines, n_sup, n_over


def _slot_step(p: cache_mod.CacheParams, k_max: int, cmax, n_p: int,
               consts, carry, xs):
    """One epoch slot for one row: the shared scan body.

    Both the full-program scan (:func:`_run_dynamic`) and the streaming
    segment path (:func:`run_dynamic` with ``segment_slots``) run exactly
    this function, so splitting a trace into segments threads identical
    arithmetic through the carry — segmented and resident epoch programs
    are bitwise-equal (test-enforced).
    """
    (flag, npg, bud, thr, per, cap, ssd_t, l1cap, s_w, s_m, s_p,
     ptl, page_ids, pvalid, rank) = consts
    lpp = jnp.int32(LINES_PER_PAGE)
    l1p, l2p, stats, t, pmap, counts, mig_rd, mig_wr, eidx = carry
    a_s, w_s, c_s, tr_s, v_s = xs
    page = jnp.clip(a_s // lpp, 0, n_p - 1)
    intent = pmap[page]
    # dynamic rows: page map decides DRAM vs the precomputed CXL
    # target (level-2 pages hit the SSD target instead); static rows
    # use the precomputed target verbatim
    tgt = jnp.where(flag != 0,
                    jnp.where(intent == 0, 0,
                              jnp.where(intent >= 2, ssd_t, tr_s)), tr_s)
    acc_t = v_s.sum().astype(jnp.int32)
    acc_d = (v_s & (jnp.where(flag != 0, intent, tgt) == 0)) \
        .sum().astype(jnp.int32)
    # sampled rows (s_p > 0): slots outside [s_w, s_w + s_m) of each
    # period functionally warm — the state machine below still runs
    # full fidelity, only the stat deltas are masked off afterwards
    pos = eidx % jnp.maximum(s_p, jnp.int32(1))
    meas = jnp.where(s_p > 0, (pos >= s_w) & (pos < s_w + s_m), True) \
        .astype(jnp.int32)
    stats0 = stats
    (l1p, l2p, stats, t), _ = jax.lax.scan(
        functools.partial(cache_mod._packed_step, p),
        (l1p, l2p, stats, t),
        (a_s, w_s.astype(bool), c_s, tgt.astype(jnp.int32), v_s),
        unroll=2)
    stats = stats0 + (stats - stats0) * meas
    counts = counts.at[page].add(v_s.astype(jnp.int32))
    eidx = eidx + 1
    boundary = (eidx % per) == 0
    do_mig = boundary & (bud > 0)
    new_pmap, pro_tl, dem_tl, n_pro, n_dem = _migration_step(
        pmap, counts, ptl, page_ids, pvalid, rank,
        bud, thr, cap, do_mig, cmax, n_p, k_max)
    # promotions read the page from its CXL endpoints + write it
    # to DRAM; demotions read DRAM + write the CXL endpoints
    mig_rd = mig_rd + pro_tl.at[0].add(n_dem * lpp)
    mig_wr = mig_wr + dem_tl.at[0].add(n_pro * lpp)
    # three-tier rows: SSD→CXL promotion reads the SSD target and
    # writes the page's CXL endpoints; CXL→SSD demotion the reverse
    do_ssd = do_mig & (ssd_t > 0)
    new_pmap, sup_tl, over_tl, n_sup, n_over = _ssd_stage(
        new_pmap, counts, ptl, page_ids, pvalid, rank,
        bud, thr, l1cap, do_ssd, cmax, n_p, k_max)
    mig_rd = mig_rd + over_tl.at[ssd_t].add(n_sup * lpp)
    mig_wr = mig_wr + sup_tl.at[ssd_t].add(n_over * lpp)
    counts = jnp.where(boundary, 0, counts)
    ys = jnp.stack([acc_t, acc_d, n_pro + n_sup, n_dem + n_over])
    carry = (l1p, l2p, stats, t, new_pmap, counts,
             mig_rd, mig_wr, eidx)
    return carry, (ys, stats, meas)


@functools.partial(jax.jit, static_argnums=(0,))
def init_dyn_carry(p: cache_mod.CacheParams, page_map0: Array):
    """Fresh batched epoch carry, leading axis B (from ``page_map0``).

    Layout: ``(l1p, l2p, stats, t, page_map, counts, mig_rd, mig_wr,
    eidx)`` — the packed cache state of :func:`repro.core.engine.
    init_batch_carry` extended with the tierer's scan state (page→tier
    map, per-page epoch counters, per-target migration totals, and the
    epoch-slot index that keeps boundary firing consistent across
    streamed segments).
    """
    page_map0 = jnp.asarray(page_map0, jnp.int32)
    b, n_p = page_map0.shape
    n_t = p.n_targets
    l1p, l2p = cache_mod.pack_state(cache_mod.init_state(p))
    bcast = lambda x: jnp.broadcast_to(x[None], (b,) + x.shape)
    return (bcast(l1p), bcast(l2p),
            jnp.zeros((b, cache_mod.nstats(n_t)), jnp.int32),
            jnp.ones((b,), jnp.int32),
            page_map0,
            jnp.zeros((b, n_p), jnp.int32),
            jnp.zeros((b, n_t), jnp.int32),
            jnp.zeros((b, n_t), jnp.int32),
            jnp.zeros((b,), jnp.int32))


def _run_dynamic_segment_impl(p: cache_mod.CacheParams, k_max: int,
                              count_bound: int, carry, addr: Array,
                              is_write: Array, core: Array, tier: Array,
                              dyn_flag: Array, n_pages: Array,
                              budget: Array, threshold: Array,
                              period: Array, dram_cap: Array,
                              ssd_tid: Array, cxl_cap: Array,
                              page_target_lines: Array,
                              s_warm: Array, s_meas: Array,
                              s_per: Array):
    """Advance the batched epoch carry over a (B, E_seg, slot_len) slice.

    Returns ``(carry, slots, snaps, meas)`` with the per-slot counters,
    cumulative stat snapshots and measurement flags of just this
    segment.
    """
    n_p = page_target_lines.shape[1]
    cmax = jnp.int32(count_bound)
    valid = addr != SENTINEL

    def one(c, a, w, cr, tr, v, flag, npg, bud, thr, per, cap, ssd_t,
            l1cap, ptl, sw, sm, sp):
        page_ids = jnp.arange(n_p, dtype=jnp.int32)
        pvalid = page_ids < npg
        rank = jnp.arange(k_max, dtype=jnp.int32)
        consts = (flag, npg, bud, thr, per, cap, ssd_t, l1cap, sw, sm,
                  sp, ptl, page_ids, pvalid, rank)
        body = functools.partial(_slot_step, p, k_max, cmax, n_p, consts)
        c, (slots, snaps, meas) = jax.lax.scan(body, c, (a, w, cr, tr, v))
        return c, slots, snaps, meas

    return jax.vmap(one)(carry, addr, is_write, core, tier, valid,
                         dyn_flag, n_pages, budget, threshold, period,
                         dram_cap, ssd_tid, cxl_cap, page_target_lines,
                         s_warm, s_meas, s_per)


@functools.lru_cache(maxsize=None)
def _dyn_segment_stepper(donate: bool):
    """Jitted epoch-segment step; carry buffers donated off-CPU."""
    return jax.jit(_run_dynamic_segment_impl, static_argnums=(0, 1, 2),
                   donate_argnums=(3,) if donate else ())


def run_dynamic_segment(p: cache_mod.CacheParams, k_max: int,
                        count_bound: int, carry, addr, is_write, core,
                        tier, dyn_flag, n_pages, budget, threshold,
                        period, dram_cap, ssd_tid, cxl_cap,
                        page_target_lines,
                        s_warm=None, s_meas=None, s_per=None,
                        *, donate: bool = False,
                        backend: str = "reference"):
    """One streamed epoch segment (public wrapper; see
    :func:`_run_dynamic_segment_impl`).  ``donate=True`` lets XLA reuse
    the previous carry's buffers on non-CPU backends.

    ``backend='pallas'`` dispatches to the epoch-structured kernel
    (:func:`repro.kernels.ops.mesi_dyn_segment`); both backends advance
    the identical 9-tuple carry and return bitwise-equal per-slot
    outputs, so segments may alternate backends freely (test-enforced).
    """
    b = jnp.asarray(dyn_flag, jnp.int32).shape[0]
    z = jnp.zeros((b,), jnp.int32)
    s_warm = z if s_warm is None else jnp.asarray(s_warm, jnp.int32)
    s_meas = z if s_meas is None else jnp.asarray(s_meas, jnp.int32)
    s_per = z if s_per is None else jnp.asarray(s_per, jnp.int32)
    ssd_tid = z if ssd_tid is None else jnp.asarray(ssd_tid, jnp.int32)
    cxl_cap = (jnp.full((b,), UNBOUNDED_PAGES, jnp.int32)
               if cxl_cap is None else jnp.asarray(cxl_cap, jnp.int32))
    if backend == "pallas":
        from repro.kernels import ops
        return ops.mesi_dyn_segment(
            carry, addr, is_write, core, tier, dyn_flag, n_pages, budget,
            threshold, period, dram_cap, ssd_tid, cxl_cap,
            page_target_lines, s_warm, s_meas, s_per, params=p,
            k_max=int(k_max), count_bound=int(count_bound))
    if backend != "reference":
        raise ValueError(f"unknown backend {backend!r}; "
                         "pick from ('reference', 'pallas')")
    donate = donate and jax.default_backend() != "cpu"
    return _dyn_segment_stepper(donate)(
        p, k_max, count_bound, carry, addr, is_write, core, tier,
        dyn_flag, n_pages, budget, threshold, period, dram_cap,
        ssd_tid, cxl_cap, page_target_lines, s_warm, s_meas, s_per)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _run_dynamic(p: cache_mod.CacheParams, k_max: int, count_bound: int,
                 addr: Array, is_write: Array, core: Array, tier: Array,
                 dyn_flag: Array, page_map0: Array, n_pages: Array,
                 budget: Array, threshold: Array, period: Array,
                 dram_cap: Array, ssd_tid: Array, cxl_cap: Array,
                 page_target_lines: Array,
                 s_warm: Array, s_meas: Array, s_per: Array
                 ) -> DynOutputs:
    """The epoch-structured batch program (see :func:`run_dynamic`).

    One segment spanning every epoch slot, threaded through the same
    carry the streaming path uses.
    """
    carry = init_dyn_carry(p, page_map0)
    carry, slots, snaps, meas = _run_dynamic_segment_impl(
        p, k_max, count_bound, carry, addr, is_write, core, tier,
        dyn_flag, n_pages, budget, threshold, period, dram_cap,
        ssd_tid, cxl_cap, page_target_lines, s_warm, s_meas, s_per)
    _, _, stats, _, pmap_f, _, mig_rd, mig_wr, _ = carry
    return DynOutputs(stats, pmap_f, mig_rd, mig_wr, slots, snaps, meas)


def prep_dynamic_inputs(addr, is_write, core, tier, *, slot_len: int,
                        k_max: int, dyn_flag, page_map0, n_pages, budget,
                        threshold, period, dram_cap, page_target_lines,
                        ssd_tid=None, cxl_cap=None,
                        s_warm=None, s_meas=None, s_per=None):
    """Validate + reshape :func:`run_dynamic` inputs to slot-major form.

    The shared front half of every dynamic-tiering execution path
    (resident, streamed, and the resilient executor's checkpointed
    segment loop): reshapes the (B, N) trace arrays to (B, E, slot_len),
    clamps ``k_max`` to the page count, derives the epoch count bound
    for the injective hotness keys (raising on int32 overflow), and
    assembles the per-row scalar tuple in
    :func:`run_dynamic_segment`'s argument order.

    ``s_warm`` / ``s_meas`` / ``s_per`` are the per-row sampled-window
    scalars in scan-slot units (:func:`repro.core.sampling.
    scan_scalars`); ``None`` (or all-zero) rows measure every slot —
    the exact path.

    ``ssd_tid`` / ``cxl_cap`` are the three-tier per-row scalars
    (:class:`DynamicTiering.cxl_capacity_pages` and the route's SSD
    target id); ``None`` rows are two-tier — ``ssd_tid`` 0 and
    ``cxl_cap`` :data:`UNBOUNDED_PAGES` gate the SSD stage off.

    Returns ``(a3, w3, c3, t3, page_map0, scalars, k_max,
    count_bound)`` where ``scalars = (dyn_flag, n_pages, budget,
    threshold, period, dram_cap, ssd_tid, cxl_cap, page_target_lines,
    s_warm, s_meas, s_per)``.
    """
    with obs.span("sweep.prep") as sp:
        addr = jnp.asarray(addr, jnp.int32)
        if addr.ndim != 2:
            raise ValueError("run_dynamic expects a (B, N) batch")
        b, n = addr.shape
        if n % slot_len != 0:
            raise ValueError(f"trace length {n} is not a multiple of the "
                             f"epoch slot length {slot_len}")
        n_p = int(jnp.asarray(page_map0).shape[1])
        # a budget beyond the page count can never be spent: clamp the top-k
        # width to P (lax.top_k rejects k > minor dimension)
        k_max = min(int(k_max), n_p)
        # counts reset every epoch, so the coldness-key bound only needs to
        # exceed the longest epoch (not the trace)
        count_bound = int(np.max(np.asarray(period))) * slot_len + 1
        if (count_bound + 1) * n_p + n_p >= 2 ** 31:
            raise ValueError(
                f"epoch hotness keys overflow int32: epoch_len * n_pages = "
                f"{(count_bound - 1) * n_p}; shrink the epoch or page count")
        e = n // slot_len
        shape3 = (b, e, slot_len)

        def r3(x):
            return jnp.asarray(x, jnp.int32).reshape(shape3)

        z = jnp.zeros((b, n), jnp.int32)
        a3 = r3(addr)
        w3 = r3(z if is_write is None else is_write)
        c3 = r3(z if core is None else core)
        t3 = r3(z if tier is None else tier)
        zb = jnp.zeros((b,), jnp.int32)
        scalars = (jnp.asarray(dyn_flag, jnp.int32),
                   jnp.asarray(n_pages, jnp.int32),
                   jnp.asarray(budget, jnp.int32),
                   jnp.asarray(threshold, jnp.int32),
                   jnp.asarray(period, jnp.int32),
                   jnp.asarray(dram_cap, jnp.int32),
                   zb if ssd_tid is None else jnp.asarray(ssd_tid, jnp.int32),
                   (jnp.full((b,), UNBOUNDED_PAGES, jnp.int32)
                    if cxl_cap is None else jnp.asarray(cxl_cap, jnp.int32)),
                   jnp.asarray(page_target_lines, jnp.int32),
                   zb if s_warm is None else jnp.asarray(s_warm, jnp.int32),
                   zb if s_meas is None else jnp.asarray(s_meas, jnp.int32),
                   zb if s_per is None else jnp.asarray(s_per, jnp.int32))
        out = (a3, w3, c3, t3, jnp.asarray(page_map0, jnp.int32), scalars,
               k_max, count_bound)
        sp.ready(out)
    return out


def run_dynamic(p: cache_mod.CacheParams, addr, is_write, core, tier,
                *, slot_len: int, k_max: int, dyn_flag, page_map0,
                n_pages, budget, threshold, period, dram_cap,
                page_target_lines, ssd_tid=None, cxl_cap=None,
                s_warm=None, s_meas=None, s_per=None,
                segment_slots: Optional[int] = None,
                backend: str = "reference") -> DynOutputs:
    """Run a `(B, N)` batch under epoch-based dynamic tiering.

    One jitted device program: an outer ``lax.scan`` over ``N //
    slot_len`` epoch slots whose carry holds the cache state, the
    per-row page→tier map, the per-page epoch counters and the
    migration totals; the inner scan is the exact packed MESI step, so
    for a row that never migrates the stats are bitwise-equal to the
    static engine path.

    Parameters
    ----------
    p : CacheParams
        Cache geometry; ``p.n_targets`` sizes the stats/migration width.
    addr, is_write, core, tier : (B, N) int32 arrays
        Sentinel-padded stacked traces.  For **dynamic** rows
        (``dyn_flag != 0``) ``tier`` carries the per-line *CXL decode
        target* (:meth:`repro.core.route.RouteMap.cxl_targets_of_lines`)
        and the evolving page map decides DRAM vs that target; for
        **static** rows ``tier`` carries the final target ids verbatim.
    slot_len : int
        Epoch-scan granularity; ``N`` must be a multiple.  Each row's
        ``period`` counts slots per epoch (``epoch_len == period *
        slot_len``).
    k_max : int
        Top-k width (>= every row's budget).
    dyn_flag, n_pages, budget, threshold, period, dram_cap : (B,) int32
        Per-row scalars (static rows: flag 0, budget 0, period 1).
    page_map0 : (B, P) int32
        Initial page → {0 DRAM, 1 CXL} intent (pages >= ``n_pages[b]``
        must be 1 and are never migration-eligible).
    page_target_lines : (B, P, T) int32
        Lines of each page per CXL endpoint under the row's committed
        HDM decode (:meth:`RouteMap.page_target_lines`) — the migration
        traffic attribution table.
    ssd_tid, cxl_cap : (B,) int32, optional
        Three-tier scalars: the row's SSD target id (0 = no SSD tier)
        and the CXL-DRAM (level-1) capacity in pages before cold pages
        spill to flash.  ``None`` = every row two-tier (``ssd_tid`` 0,
        ``cxl_cap`` :data:`UNBOUNDED_PAGES`) — bitwise-equal to the
        historical two-tier program (test-enforced).
    segment_slots : int, optional
        Stream the epoch program in segments of this many slots: one
        device call per segment with the full tierer carry (cache state,
        page map, counters, migration totals, slot index) threaded
        between calls, so only one segment's trace is scanned per
        program.  Outputs are bitwise-equal to the resident scan
        (test-enforced).
    backend : str
        'reference' (vmapped epoch scan) or 'pallas'
        (:func:`repro.kernels.ops.mesi_dyn_segment`, the epoch-
        structured kernel) — bitwise-equal outputs (test-enforced).

    Returns
    -------
    DynOutputs
        Stats, final page maps, per-target migration line counts,
        per-slot counters (:data:`SLOT_FIELDS`) and cumulative stat
        snapshots at each slot boundary.
    """
    a3, w3, c3, t3, page_map0, scalars, k_max, count_bound = \
        prep_dynamic_inputs(
            addr, is_write, core, tier, slot_len=slot_len, k_max=k_max,
            dyn_flag=dyn_flag, page_map0=page_map0, n_pages=n_pages,
            budget=budget, threshold=threshold, period=period,
            dram_cap=dram_cap, page_target_lines=page_target_lines,
            ssd_tid=ssd_tid, cxl_cap=cxl_cap,
            s_warm=s_warm, s_meas=s_meas, s_per=s_per)
    b, e = a3.shape[:2]
    if segment_slots is None and backend != "reference":
        segment_slots = e   # pallas: one kernel launch spans every slot
    if segment_slots is not None and segment_slots < 1:
        raise ValueError(f"segment_slots must be >= 1, got {segment_slots}")
    with obs.span("sweep.program") as sp:
        if segment_slots is None:
            out = _run_dynamic(p, int(k_max), count_bound, a3, w3, c3, t3,
                               scalars[0], page_map0, *scalars[1:])
        else:
            out = _run_dynamic_segmented(p, int(k_max), count_bound, a3,
                                         w3, c3, t3, page_map0, scalars,
                                         segment_slots, backend)
        sp.ready(out)
        if sp:
            limit = 0
            if backend == "pallas":
                from repro.kernels import cache_sim
                limit = cache_sim.vmem_limit_bytes(
                    p, n_pages=page_map0.shape[1])
            sp.add(program="epoch", backend=backend,
                   row_steps=b * e * slot_len,
                   segments=(1 if segment_slots is None
                             else -(-e // segment_slots)),
                   vmem_limit_bytes=limit)
    return out


def _run_dynamic_segmented(p, k_max, count_bound, a3, w3, c3, t3,
                           page_map0, scalars, segment_slots, backend):
    """:func:`run_dynamic` as one device call per ``segment_slots``."""
    e = a3.shape[1]
    carry = init_dyn_carry(p, page_map0)
    slots_parts, snaps_parts, meas_parts = [], [], []
    for s in range(0, e, segment_slots):
        sl = slice(s, min(s + segment_slots, e))
        carry, slots, snaps, meas = run_dynamic_segment(
            p, k_max, count_bound, carry, a3[:, sl], w3[:, sl],
            c3[:, sl], t3[:, sl], *scalars, donate=True, backend=backend)
        slots_parts.append(slots)
        snaps_parts.append(snaps)
        meas_parts.append(meas)
    _, _, stats, _, pmap_f, _, mig_rd, mig_wr, _ = carry
    return DynOutputs(stats, pmap_f, mig_rd, mig_wr,
                      jnp.concatenate(slots_parts, axis=1),
                      jnp.concatenate(snaps_parts, axis=1),
                      jnp.concatenate(meas_parts, axis=1))


# ---------------------------------------------------------------------------
# Host twin (the parity oracle)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class HostResult:
    """NumPy replay of one row's epoch loop (:func:`host_simulate`).

    ``target`` is the per-access target-id sequence the evolving page
    map produced — feeding it to the *static* engine path must yield
    stats bitwise-equal to the device program's (test-enforced).
    """
    target: np.ndarray     # (N,) int32 per-access target id
    page_map: np.ndarray   # (P,) int32 final page map
    mig_read: np.ndarray   # (T,) int64 migration lines read per target
    mig_write: np.ndarray  # (T,) int64 migration lines written per target
    slots: np.ndarray      # (E, 4) int64, columns as SLOT_FIELDS

    @property
    def migrated_pages(self) -> int:
        return int(self.slots[:, 2].sum() + self.slots[:, 3].sum())


def host_simulate(tiering: Optional[DynamicTiering], addr, cxl_target,
                  page_map0, n_pages: int, page_target_lines,
                  slot_len: int, *, valid=None,
                  dram_capacity_pages: Optional[int] = None,
                  ssd_tid: int = 0,
                  cxl_capacity_pages: Optional[int] = None) -> HostResult:
    """Replay the device epoch loop in NumPy (single row).

    The migration decisions depend only on the trace and the map
    evolution — never on cache state — so this twin derives the exact
    per-access target sequence without simulating the cache, mirroring
    :func:`run_dynamic` decision-for-decision (same injective hotness
    keys, same capacity arithmetic).

    Parameters
    ----------
    tiering : DynamicTiering or None
        ``None`` = static row (the initial map routes every access).
    addr : (N,) int array
        Sentinel-padded line trace; ``N % slot_len == 0``.
    cxl_target : (N,) int array
        Per-line CXL decode target (what the line hits *if* CXL).
    page_map0 : (P,) int array
        Initial page → {0, 1} intent.
    n_pages : int
        Migration-eligible pages (``P`` may be padded beyond it).
    page_target_lines : (P, T) int array
        Per-page per-target line counts for migration attribution.
    slot_len : int
        Epoch-scan granularity; ``tiering.epoch_len`` must be a
        multiple.
    valid : (N,) bool array, optional
        Defaults to ``addr != SENTINEL``.
    dram_capacity_pages : int, optional
        Overrides ``tiering.dram_capacity_pages``.
    ssd_tid : int
        SSD target id of the route (0 = no SSD tier; the SSD stage
        never fires and level-2 intents are impossible).
    cxl_capacity_pages : int, optional
        Overrides ``tiering.cxl_capacity_pages``.

    Returns
    -------
    HostResult
    """
    addr = np.asarray(addr, np.int64)
    n = addr.shape[0]
    if n % slot_len != 0:
        raise ValueError(f"trace length {n} not a multiple of {slot_len}")
    cxl_target = np.asarray(cxl_target, np.int64)
    pmap = np.asarray(page_map0, np.int64).copy()
    ptl = np.asarray(page_target_lines, np.int64)
    n_p, n_t = ptl.shape
    valid = (addr != SENTINEL) if valid is None else np.asarray(valid, bool)
    if tiering is None:
        budget, threshold, period = 0, 1, 1
    else:
        if tiering.epoch_len % slot_len != 0:
            raise ValueError(f"epoch_len {tiering.epoch_len} not a "
                             f"multiple of slot_len {slot_len}")
        budget, threshold = tiering.budget, tiering.threshold
        period = tiering.epoch_len // slot_len
    cap = dram_capacity_pages
    if cap is None:
        cap = (tiering.dram_capacity_pages if tiering is not None else None)
    cap = UNBOUNDED_PAGES if cap is None else int(cap)
    l1cap = cxl_capacity_pages
    if l1cap is None:
        l1cap = (tiering.cxl_capacity_pages if tiering is not None else None)
    l1cap = UNBOUNDED_PAGES if l1cap is None else int(l1cap)
    ssd_tid = int(ssd_tid)

    e = n // slot_len
    cmax = period * slot_len + 1
    page_ids = np.arange(n_p, dtype=np.int64)
    pvalid = page_ids < n_pages
    target = np.zeros(n, np.int32)
    counts = np.zeros(n_p, np.int64)
    mig_rd = np.zeros(n_t, np.int64)
    mig_wr = np.zeros(n_t, np.int64)
    slots = np.zeros((e, 4), np.int64)
    for ei in range(e):
        sl = slice(ei * slot_len, (ei + 1) * slot_len)
        page = np.clip(addr[sl] // LINES_PER_PAGE, 0, n_p - 1)
        intent = pmap[page]
        tgt = np.where(intent == 0, 0,
                       np.where(intent >= 2, ssd_tid, cxl_target[sl]))
        target[sl] = tgt
        v = valid[sl]
        slots[ei, 0] = v.sum()
        slots[ei, 1] = (v & (intent == 0)).sum()
        np.add.at(counts, page, v.astype(np.int64))
        if (ei + 1) % period == 0:
            if budget > 0:
                hot = (pmap == 1) & pvalid & (counts >= threshold)
                n_want = min(budget, int(hot.sum()))
                free = max(cap - int(((pmap == 0) & pvalid).sum()), 0)
                n_dem_needed = min(max(n_want - free, 0), budget)
                is_dram = (pmap == 0) & pvalid
                dkey = np.where(
                    is_dram,
                    encode_hot_key(cmax - counts, page_ids, n_p, np), -1)
                dorder = np.argsort(-dkey, kind="stable")
                n_dem = min(n_dem_needed, int(is_dram.sum()))
                demote = dorder[:n_dem]
                n_pro = min(int(hot.sum()), budget, free + n_dem)
                pkey = np.where(
                    hot, encode_hot_key(counts, page_ids, n_p, np), -1)
                porder = np.argsort(-pkey, kind="stable")
                promote = porder[:n_pro]
                pmap[promote] = 0
                pmap[demote] = 1
                mig_rd += ptl[promote].sum(axis=0)
                mig_rd[0] += n_dem * LINES_PER_PAGE
                mig_wr += ptl[demote].sum(axis=0)
                mig_wr[0] += n_pro * LINES_PER_PAGE
                slots[ei, 2] = n_pro
                slots[ei, 3] = n_dem
                if ssd_tid > 0:
                    # SSD stage (mirrors _ssd_stage): hot level-2 pages
                    # promote to CXL, then level-1 overflow spills back
                    hot2 = (pmap == 2) & pvalid & (counts >= threshold)
                    skey = np.where(
                        hot2, encode_hot_key(counts, page_ids, n_p, np), -1)
                    sorder = np.argsort(-skey, kind="stable")
                    n_sup = min(budget, int(hot2.sum()))
                    sup = sorder[:n_sup]
                    pmap[sup] = 1
                    is_l1 = (pmap == 1) & pvalid
                    over = min(max(int(is_l1.sum()) - l1cap, 0), budget)
                    okey = np.where(
                        is_l1,
                        encode_hot_key(cmax - counts, page_ids, n_p, np),
                        -1)
                    oorder = np.argsort(-okey, kind="stable")
                    n_over = min(over, int(is_l1.sum()))
                    down = oorder[:n_over]
                    pmap[down] = 2
                    mig_rd += ptl[down].sum(axis=0)
                    mig_rd[ssd_tid] += n_sup * LINES_PER_PAGE
                    mig_wr += ptl[sup].sum(axis=0)
                    mig_wr[ssd_tid] += n_over * LINES_PER_PAGE
                    slots[ei, 2] += n_sup
                    slots[ei, 3] += n_over
            counts[:] = 0
    return HostResult(target=target, page_map=pmap.astype(np.int32),
                      mig_read=mig_rd, mig_write=mig_wr, slots=slots)


# ---------------------------------------------------------------------------
# Reporting helpers
# ---------------------------------------------------------------------------
def epoch_fractions(slots: np.ndarray, period: int) -> List[float]:
    """Per-epoch DRAM hit-tier fractions from per-slot counters.

    Aggregates the (E, 4) slot counters into groups of ``period`` slots
    (one true epoch each; a trailing partial group becomes a partial
    epoch) and returns ``acc_dram / acc_total`` per epoch.  Trailing
    all-sentinel epochs — batch padding beyond this row's trace — are
    dropped; an empty epoch *between* real ones reports 0.0.
    """
    slots = np.asarray(slots, np.int64)
    out: List[float] = []
    last_real = -1
    for s in range(0, slots.shape[0], period):
        grp = slots[s:s + period]
        tot = int(grp[:, 0].sum())
        if tot:
            last_real = len(out)
        out.append(float(grp[:, 1].sum()) / tot if tot else 0.0)
    return out[:last_real + 1]

"""Two-level set-associative MESI cache simulator (vectorized, JAX).

Models the paper's Table-I host: up to N cores with private L1s and a shared
L2/LLC under a MESI directory ("Two-level, Directory-based").  gem5 walks a
C++ event queue per access; the TPU-native re-think keeps *trace order*
sequential (a `lax.scan`) but makes every per-access operation — tag compare
across ways, LRU victim select, directory sharer updates — a data-parallel
array op.  The Pallas kernel in :mod:`repro.kernels.cache_sim` runs the same
state machine with the tag store resident in VMEM; this module is its oracle
(`ref`).

The simulator tracks, per access, which memory *target* backs the line —
0 = local DRAM, 1..n_targets-1 = CXL expander endpoints, as routed by the
page-placement policy (:mod:`repro.core.numa`) through the committed HDM
interleave programs (:mod:`repro.core.route`); the binary DRAM/CXL machine
is the `n_targets == 2` special case.  Misses/writebacks are priced per
target by :mod:`repro.core.machine` and the
**cache pollution** effect of CXL traffic (CXL-destined lines evicting
DRAM-destined ones) falls out of the LRU state, exactly the effect the paper
highlights.

State encoding (per line): tag int32 (-1 invalid), last-use int32, MESI
state int32 {I=0,S=1,E=2,M=3}, tier int32, plus an L2 directory bitmask of
L1 sharers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

# MESI states
I, S, E, M = 0, 1, 2, 3

# Sentinel-padding convention: padded trace entries carry this address
# (real line addresses are >= 0); gated steps and the Pallas kernels skip
# all state/stat updates for them.  Single source of truth — the engine and
# the kernels import it from here.
SENTINEL = -1

# ---- stats layout ----------------------------------------------------------
# The layout is parameterized by the number of memory *targets* the routed
# lines can hit (target 0 = local DRAM, targets 1..T-1 = CXL expanders, see
# repro.core.route): 4 base counters, then T per-target memory reads, then T
# per-target memory writes, then 4 coherence counters.  For the binary-tier
# case (T == 2: DRAM + one CXL pool) this is exactly the historical 12-slot
# layout, so the legacy module-level constants stay valid.
L1_HIT, L1_MISS, L2_HIT, L2_MISS = 0, 1, 2, 3
MEM_READ = 4                       # base of the per-target read counters


def mem_write_base(n_targets: int = 2) -> int:
    """First index of the per-target memory-write counters."""
    return MEM_READ + n_targets


def coherence_base(n_targets: int = 2) -> int:
    """Index of `upgrades` (first of the 4 coherence counters)."""
    return MEM_READ + 2 * n_targets


def nstats(n_targets: int = 2) -> int:
    return 8 + 2 * n_targets


def stat_names(n_targets: int = 2) -> Tuple[str, ...]:
    """Counter names for a `n_targets`-wide stats vector.

    T == 2 keeps the historical dram/cxl names; T > 2 names the CXL targets
    `cxl0..cxl{T-2}` (target ids 1..T-1).
    """
    if n_targets == 2:
        mem = ("mem_read_dram", "mem_read_cxl",
               "mem_write_dram", "mem_write_cxl")
    else:
        cxl = [f"cxl{k}" for k in range(n_targets - 1)]
        mem = tuple(["mem_read_dram"] + [f"mem_read_{c}" for c in cxl]
                    + ["mem_write_dram"] + [f"mem_write_{c}" for c in cxl])
    return ("l1_hit", "l1_miss", "l2_hit", "l2_miss", *mem,
            "upgrades", "invalidations", "back_invalidations",
            "writebacks_l1")


# Legacy binary-tier (T == 2) indices — single source of truth for every
# consumer of the 12-slot layout (machine, kernels, tests).
MEM_READ_DRAM, MEM_READ_CXL = 4, 5
MEM_WRITE_DRAM, MEM_WRITE_CXL = 6, 7
UPGRADES, INVALIDATIONS, BACK_INVALIDATIONS, WRITEBACKS_L1 = 8, 9, 10, 11
NSTATS = nstats(2)
STAT_NAMES = stat_names(2)


@dataclasses.dataclass(frozen=True)
class CacheParams:
    """Geometry: sizes in bytes; sets derived (power of two enforced).

    `n_targets` sizes the stats vector (see the stats-layout block above):
    the `tier` trace field carries target ids in [0, n_targets).  The
    default 2 is the binary DRAM/CXL machine.
    """
    l1_bytes: int = 64 * 1024
    l1_ways: int = 8
    l2_bytes: int = 2 * 1024 * 1024
    l2_ways: int = 16
    line_bytes: int = 64
    cores: int = 1
    n_targets: int = 2

    @property
    def l1_sets(self) -> int:
        s = self.l1_bytes // (self.l1_ways * self.line_bytes)
        if s <= 0 or s & (s - 1) != 0:
            raise ValueError(f"L1 sets must be a power of two, got {s}")
        return s

    @property
    def l2_sets(self) -> int:
        s = self.l2_bytes // (self.l2_ways * self.line_bytes)
        if s <= 0 or s & (s - 1) != 0:
            raise ValueError(f"L2 sets must be a power of two, got {s}")
        return s

    @property
    def state_bytes(self) -> int:
        """Bytes of one row's cache state: 3 int32 fields a line in the
        L1s (tag, use, MESI state), 5 in the L2 (and tier, sharers)."""
        return 4 * (3 * self.cores * self.l1_sets * self.l1_ways
                    + 5 * self.l2_sets * self.l2_ways)


class CacheState(NamedTuple):
    l1_tag: Array     # (cores, l1_sets, l1_ways) int32, -1 invalid
    l1_use: Array     # (cores, l1_sets, l1_ways) int32 last-use time
    l1_state: Array   # (cores, l1_sets, l1_ways) int32 MESI
    l2_tag: Array     # (l2_sets, l2_ways) int32
    l2_use: Array     # (l2_sets, l2_ways) int32
    l2_state: Array   # (l2_sets, l2_ways) int32 (M == dirty-in-L2)
    l2_tier: Array    # (l2_sets, l2_ways) int32 backing tier of the line
    l2_sharers: Array # (l2_sets, l2_ways) int32 bitmask of L1 sharers


def init_state(p: CacheParams) -> CacheState:
    def full(shape):
        return jnp.full(shape, -1, jnp.int32)
    z1 = (p.cores, p.l1_sets, p.l1_ways)
    z2 = (p.l2_sets, p.l2_ways)
    return CacheState(
        l1_tag=full(z1), l1_use=jnp.zeros(z1, jnp.int32),
        l1_state=jnp.zeros(z1, jnp.int32),
        l2_tag=full(z2), l2_use=jnp.zeros(z2, jnp.int32),
        l2_state=jnp.zeros(z2, jnp.int32),
        l2_tier=jnp.zeros(z2, jnp.int32),
        l2_sharers=jnp.zeros(z2, jnp.int32),
    )


def _l2_lookup(st: CacheState, addr: Array, p: CacheParams):
    set2 = addr & (p.l2_sets - 1)
    row = st.l2_tag[set2]                          # (ways,)
    hits = row == addr
    hit = hits.any()
    way = jnp.argmax(hits)
    victim = jnp.argmin(st.l2_use[set2])
    return set2, hit, jnp.where(hit, way, victim).astype(jnp.int32)


def _step(p: CacheParams, carry, x, valid=None):
    """One access through the two-level MESI hierarchy.

    `valid` (optional scalar bool) gates every state write and stat
    increment: when False the access is a sentinel-padding entry (see
    :data:`repro.core.engine.SENTINEL`) and must leave the carry untouched.
    The gate folds into the existing update conditions (`& valid` on masks,
    `* valid` on counter amounts), so for valid accesses the integer
    arithmetic is bitwise-identical to the ungated step — at ~zero extra
    cost compared to a post-hoc select over the full state arrays.
    """
    st, stats, t = carry
    addr, is_write, core, tier = x
    addr = addr.astype(jnp.int32)
    core = core.astype(jnp.int32)
    wbase = mem_write_base(p.n_targets)
    upg, inval, binval, wb1 = (coherence_base(p.n_targets) + k
                               for k in range(4))
    if valid is None:
        gate = lambda cond: cond
        put = lambda old, new: new
        inc = lambda s, idx, amt=1: s.at[idx].add(amt)
    else:
        vi = valid.astype(jnp.int32)
        gate = lambda cond: cond & valid
        put = lambda old, new: jnp.where(valid, new, old)
        inc = lambda s, idx, amt=1: s.at[idx].add(amt * vi)

    # ---------------- L1 lookup ----------------
    set1 = addr & (p.l1_sets - 1)
    row_t = st.l1_tag[core, set1]                   # (l1_ways,)
    row_s = st.l1_state[core, set1]
    hits = (row_t == addr) & (row_s != I)
    l1_hit = hits.any()
    way_hit = jnp.argmax(hits).astype(jnp.int32)
    victim1 = jnp.argmin(st.l1_use[core, set1]).astype(jnp.int32)
    way1 = jnp.where(l1_hit, way_hit, victim1)

    cur_state = row_s[way1]
    # write-hit on S needs an upgrade: invalidate other cores' copies
    needs_upgrade = l1_hit & is_write & (cur_state == S)
    # find all other L1 copies of this line (directory-equivalent probe)
    copies = (st.l1_tag[:, set1] == addr) & (st.l1_state[:, set1] != I)
    other = copies & (jnp.arange(p.cores, dtype=jnp.int32)[:, None] != core)
    n_other = other.sum()

    stats = inc(stats, L1_HIT, l1_hit.astype(jnp.int32))
    stats = inc(stats, L1_MISS, (~l1_hit).astype(jnp.int32))
    stats = inc(stats, upg, (needs_upgrade).astype(jnp.int32))
    stats = inc(stats, inval,
                jnp.where(is_write, n_other, 0).astype(jnp.int32))

    # invalidate other copies on any write (upgrade or RFO fill)
    inval_mask = gate(other & is_write)
    new_l1_state = jnp.where(
        inval_mask, I, st.l1_state[:, set1])        # (cores, ways)
    st = st._replace(l1_state=st.l1_state.at[:, set1].set(new_l1_state))

    # ---------------- L1 victim writeback (on miss) ----------------
    evict_valid = (~l1_hit) & (st.l1_state[core, set1, way1] != I)
    evict_tag = st.l1_tag[core, set1, way1]
    evict_dirty = evict_valid & (st.l1_state[core, set1, way1] == M)
    # inclusive L2: evicted line is present; mark M (dirty) there, drop sharer
    eset2, ehit, eway2 = _l2_lookup(st, evict_tag, p)
    do_wb = gate(evict_dirty & ehit)
    st = st._replace(
        l2_state=st.l2_state.at[eset2, eway2].set(
            jnp.where(do_wb, M, st.l2_state[eset2, eway2])),
        l2_sharers=st.l2_sharers.at[eset2, eway2].set(
            jnp.where(gate(evict_valid & ehit),
                      st.l2_sharers[eset2, eway2] & ~(1 << core),
                      st.l2_sharers[eset2, eway2])))
    stats = inc(stats, wb1, evict_dirty.astype(jnp.int32))

    # ---------------- L2 lookup (only meaningful on L1 miss) --------------
    set2, l2_hit_raw, way2 = _l2_lookup(st, addr, p)
    l2_hit = l2_hit_raw & (~l1_hit)
    l2_miss = (~l2_hit_raw) & (~l1_hit)
    stats = inc(stats, L2_HIT, l2_hit.astype(jnp.int32))
    stats = inc(stats, L2_MISS, l2_miss.astype(jnp.int32))

    # ---- L2 victim handling on fill: back-invalidate + writeback ----
    v_tag = st.l2_tag[set2, way2]
    v_state = st.l2_state[set2, way2]
    v_tier = st.l2_tier[set2, way2]
    v_valid = l2_miss & (v_state != I) & (v_tag != addr)
    # back-invalidate L1 copies of the victim (inclusive hierarchy)
    vset1 = v_tag & (p.l1_sets - 1)
    v_copies = (st.l1_tag[:, vset1] == v_tag) & (st.l1_state[:, vset1] != I)
    v_l1_dirty = (v_copies & (st.l1_state[:, vset1] == M)).any()
    st = st._replace(l1_state=st.l1_state.at[:, vset1].set(
        jnp.where(v_copies & gate(v_valid), I, st.l1_state[:, vset1])))
    stats = inc(stats, binval,
                jnp.where(v_valid, v_copies.sum(), 0).astype(jnp.int32))
    v_dirty = v_valid & ((v_state == M) | v_l1_dirty)
    stats = inc(stats, wbase + v_tier, v_dirty.astype(jnp.int32))

    # ---- memory read on L2 miss ----
    stats = inc(stats, MEM_READ + tier, l2_miss.astype(jnp.int32))

    # ---- install / update line in L2 ----
    fill2 = gate(l2_miss)
    touch2 = gate(l2_hit | l2_miss)
    st = st._replace(
        l2_tag=st.l2_tag.at[set2, way2].set(
            jnp.where(fill2, addr, st.l2_tag[set2, way2])),
        l2_tier=st.l2_tier.at[set2, way2].set(
            jnp.where(fill2, tier, st.l2_tier[set2, way2])),
        l2_state=st.l2_state.at[set2, way2].set(
            jnp.where(fill2, E, st.l2_state[set2, way2])),
        l2_use=st.l2_use.at[set2, way2].set(
            jnp.where(touch2, t, st.l2_use[set2, way2])),
        l2_sharers=st.l2_sharers.at[set2, way2].set(
            jnp.where(fill2, 1 << core,
                      jnp.where(gate(l2_hit),
                                st.l2_sharers[set2, way2] | (1 << core),
                                st.l2_sharers[set2, way2]))))

    # ---------------- install / update line in L1 ----------------
    # new state: write -> M; read fill -> E if sole sharer else S
    sole = n_other == 0
    fill_state = jnp.where(is_write, M, jnp.where(sole, E, S)).astype(jnp.int32)
    hit_state = jnp.where(is_write, M, cur_state).astype(jnp.int32)
    new_state = jnp.where(l1_hit, hit_state, fill_state)
    st = st._replace(
        l1_tag=st.l1_tag.at[core, set1, way1].set(
            put(st.l1_tag[core, set1, way1], addr)),
        l1_state=st.l1_state.at[core, set1, way1].set(
            put(st.l1_state[core, set1, way1], new_state)),
        l1_use=st.l1_use.at[core, set1, way1].set(
            put(st.l1_use[core, set1, way1], t)))

    return (st, stats, t + 1), None


def _gated_step(p: CacheParams, carry, x):
    """`_step` with a per-access validity gate (sentinel-padding support).

    `x` carries a fifth element `valid`; when it is False the access is a
    sentinel (see :data:`repro.core.engine.SENTINEL`) and neither the cache
    state nor the stats vector changes — the gate folds into the step's own
    update masks (sentinel addresses index safely: `-1 & (sets-1)` is in
    range), so for valid accesses the arithmetic — and therefore the
    stats — is bitwise identical to the ungated `_step`.  The logical time
    `t` advances regardless, matching the position-based timestamps of the
    Pallas backend; padding must therefore only ever be appended at the
    *end* of a trace.
    """
    addr, is_write, core, tier, valid = x
    return _step(p, carry, (addr, is_write, core, tier), valid=valid)


# ---------------------------------------------------------------------------
# Packed-state step: the batched engine's fast path
# ---------------------------------------------------------------------------
# Under `jax.vmap`, every `.at[...]` state write becomes a batched scatter —
# ~0.5 us each on CPU, and `_step` issues ~24 of them (12 are the stats
# counter bumps).  The packed representation stacks the per-line planes into
# trailing axes — L1 (cores, sets, ways, 3)=[tag,use,state], L2 (sets, ways,
# 5)=[tag,use,state,tier,sharers] — so each hierarchy update is ONE write of
# a small block, and the stats vector accumulates by a single vector add of
# the 12 per-access increments.  Same state machine, same intra-step
# read/write order, integer-for-integer the same arithmetic: stats and final
# state are bitwise-equal to `_step` (enforced by tests/test_engine.py).

def pack_state(st: CacheState):
    l1p = jnp.stack([st.l1_tag, st.l1_use, st.l1_state], axis=-1)
    l2p = jnp.stack([st.l2_tag, st.l2_use, st.l2_state, st.l2_tier,
                     st.l2_sharers], axis=-1)
    return l1p, l2p


def unpack_state(l1p, l2p) -> CacheState:
    return CacheState(
        l1_tag=l1p[..., 0], l1_use=l1p[..., 1], l1_state=l1p[..., 2],
        l2_tag=l2p[..., 0], l2_use=l2p[..., 1], l2_state=l2p[..., 2],
        l2_tier=l2p[..., 3], l2_sharers=l2p[..., 4])


def _packed_step(p: CacheParams, carry, x):
    """One (optionally sentinel-gated) access over packed state.

    Mirrors `_step` operation-for-operation; `valid=False` entries leave
    state and stats untouched.  When `p.cores == 1` the cross-core MESI
    traffic (other-copy probe, write-invalidations) is statically absent —
    `other` is identically false — and is elided at trace time.
    """
    l1p, l2p, stats, t = carry
    addr, is_write, core, tier, valid = x
    addr = addr.astype(jnp.int32)
    core = core.astype(jnp.int32)
    vi = valid.astype(jnp.int32)

    # ---------------- L1 lookup ----------------
    set1 = addr & (p.l1_sets - 1)
    all1 = l1p[:, set1]                           # (cores, ways, 3)
    row_t, row_u, row_s = (all1[core, :, 0], all1[core, :, 1],
                           all1[core, :, 2])
    hits = (row_t == addr) & (row_s != I)
    l1_hit = hits.any()
    way1 = jnp.where(l1_hit, jnp.argmax(hits),
                     jnp.argmin(row_u)).astype(jnp.int32)
    cur_state = row_s[way1]
    needs_upgrade = l1_hit & is_write & (cur_state == S)

    if p.cores == 1:
        n_other = jnp.int32(0)
    else:
        copies = (all1[:, :, 0] == addr) & (all1[:, :, 2] != I)
        other = copies & (jnp.arange(p.cores, dtype=jnp.int32)[:, None]
                          != core)
        n_other = other.sum()
        # invalidate other copies on any write (upgrade or RFO fill)
        inval_mask = other & is_write & valid
        l1p = l1p.at[:, set1, :, 2].set(
            jnp.where(inval_mask, I, all1[:, :, 2]))

    # ---------------- L1 victim writeback (on miss) ----------------
    evict_valid = (~l1_hit) & (cur_state != I)
    evict_tag = row_t[way1]
    evict_dirty = evict_valid & (cur_state == M)
    eset2 = evict_tag & (p.l2_sets - 1)
    erow = l2p[eset2]                             # (ways, 5)
    ehits = erow[:, 0] == evict_tag
    ehit = ehits.any()
    eway = jnp.where(ehit, jnp.argmax(ehits),
                     jnp.argmin(erow[:, 1])).astype(jnp.int32)
    ecell = erow[eway]
    ecell = ecell.at[2].set(jnp.where(evict_dirty & ehit & valid,
                                      M, ecell[2]))
    ecell = ecell.at[4].set(jnp.where(evict_valid & ehit & valid,
                                      ecell[4] & ~(1 << core), ecell[4]))
    l2p = l2p.at[eset2, eway].set(ecell)

    # ---------------- L2 lookup (only meaningful on L1 miss) --------------
    set2 = addr & (p.l2_sets - 1)
    row2 = l2p[set2]
    hits2 = row2[:, 0] == addr
    l2_hit_raw = hits2.any()
    way2 = jnp.where(l2_hit_raw, jnp.argmax(hits2),
                     jnp.argmin(row2[:, 1])).astype(jnp.int32)
    l2_hit = l2_hit_raw & (~l1_hit)
    l2_miss = (~l2_hit_raw) & (~l1_hit)

    # ---- L2 victim handling on fill: back-invalidate + writeback ----
    v_cell = l2p[set2, way2]
    v_tag, v_state, v_tier = v_cell[0], v_cell[2], v_cell[3]
    v_valid = l2_miss & (v_state != I) & (v_tag != addr)
    vset1 = v_tag & (p.l1_sets - 1)
    vall = l1p[:, vset1]
    v_copies = (vall[:, :, 0] == v_tag) & (vall[:, :, 2] != I)
    v_l1_dirty = (v_copies & (vall[:, :, 2] == M)).any()
    l1p = l1p.at[:, vset1, :, 2].set(
        jnp.where(v_copies & (v_valid & valid), I, vall[:, :, 2]))
    v_dirty = v_valid & ((v_state == M) | v_l1_dirty)

    # ---- install / update line in L2 ----
    fill2 = l2_miss & valid
    touch2 = (l2_hit | l2_miss) & valid
    me = jnp.int32(1) << core
    l2p = l2p.at[set2, way2].set(jnp.stack([
        jnp.where(fill2, addr, v_cell[0]),
        jnp.where(touch2, t, v_cell[1]),
        jnp.where(fill2, E, v_cell[2]),
        jnp.where(fill2, tier, v_cell[3]),
        jnp.where(fill2, me,
                  jnp.where(l2_hit & valid, v_cell[4] | me, v_cell[4])),
    ]))

    # ---------------- install / update line in L1 ----------------
    sole = n_other == 0
    fill_state = jnp.where(is_write, M,
                           jnp.where(sole, E, S)).astype(jnp.int32)
    hit_state = jnp.where(is_write, M, cur_state).astype(jnp.int32)
    new_state = jnp.where(l1_hit, hit_state, fill_state)
    old1 = l1p[core, set1, way1]
    l1p = l1p.at[core, set1, way1].set(
        jnp.where(valid, jnp.stack([addr, t, new_state]), old1))

    # ---- stats: one vector add, rows ordered as stat_names(n_targets) ----
    z = jnp.int32(0)
    incs = jnp.stack(
        [l1_hit.astype(jnp.int32), (~l1_hit).astype(jnp.int32),
         l2_hit.astype(jnp.int32), l2_miss.astype(jnp.int32)]
        + [(l2_miss & (tier == k)).astype(jnp.int32)
           for k in range(p.n_targets)]
        + [(v_dirty & (v_tier == k)).astype(jnp.int32)
           for k in range(p.n_targets)]
        + [needs_upgrade.astype(jnp.int32),
           jnp.where(is_write, n_other, z).astype(jnp.int32),
           jnp.where(v_valid, v_copies.sum(), z).astype(jnp.int32),
           evict_dirty.astype(jnp.int32)])
    stats = stats + incs * vi
    return (l1p, l2p, stats, t + 1), None


@functools.partial(jax.jit, static_argnums=0)
def simulate_trace(p: CacheParams, state: CacheState,
                   addr: Array, is_write: Array,
                   core: Array | None = None,
                   tier: Array | None = None
                   ) -> Tuple[CacheState, Array]:
    """Run a trace through the hierarchy.

    Args:
      addr:     (N,) int32 cacheline indices (window-relative).
      is_write: (N,) bool.
      core:     (N,) int32 issuing core (default 0).
      tier:     (N,) int32 backing target per access (0=DRAM, 1..=CXL
                targets; default 0).

    Returns: (final_state, stats[nstats(p.n_targets)] int32) — see
    `stat_names(p.n_targets)`.
    """
    n = addr.shape[0]
    core = jnp.zeros(n, jnp.int32) if core is None else core.astype(jnp.int32)
    tier = jnp.zeros(n, jnp.int32) if tier is None else tier.astype(jnp.int32)
    xs = (addr.astype(jnp.int32), is_write.astype(bool), core, tier)
    stats0 = jnp.zeros((nstats(p.n_targets),), jnp.int32)
    (st, stats, _), _ = jax.lax.scan(
        functools.partial(_step, p), (state, stats0, jnp.int32(1)), xs)
    return st, stats


def stats_dict(stats: Array) -> Dict[str, int]:
    """Counter dict; the target count is inferred from the vector width."""
    t = (len(stats) - 8) // 2
    return {n: int(v) for n, v in zip(stat_names(t), stats)}


def snapshot_deltas(snapshots) -> "np_mod.ndarray":
    """Per-epoch counter deltas from cumulative stat snapshots.

    The dynamic-tiering scan (:mod:`repro.core.tiering_dyn`) emits the
    cumulative stats vector at every epoch-slot boundary; this turns the
    ``(E, nstats)`` snapshot stack into per-slot deltas — row ``e`` is
    exactly the counters epoch slot ``e`` contributed, so per-epoch miss
    rates and per-epoch tier traffic splits fall out of the standard
    :func:`stats_dict` machinery.
    """
    import numpy as np_mod
    s = np_mod.asarray(snapshots, np_mod.int64)
    if s.ndim != 2:
        raise ValueError(f"snapshots must be (E, nstats), got {s.shape}")
    return np_mod.diff(s, axis=0, prepend=np_mod.zeros((1, s.shape[1]),
                                                       np_mod.int64))


def dram_traffic_fraction(delta_stats, n_targets: int = 2):
    """DRAM share of memory-line traffic per snapshot delta row.

    ``(mem_read_dram + mem_write_dram) / (all reads + writes)`` for each
    row of a :func:`snapshot_deltas` result; rows with no memory traffic
    report 0.0.
    """
    import numpy as np_mod
    d = np_mod.asarray(delta_stats, np_mod.int64)
    wb = mem_write_base(n_targets)
    reads = d[:, MEM_READ:MEM_READ + n_targets]
    writes = d[:, wb:wb + n_targets]
    total = reads.sum(axis=1) + writes.sum(axis=1)
    dram = reads[:, 0] + writes[:, 0]
    return np_mod.where(total > 0, dram / np_mod.maximum(total, 1), 0.0)


def miss_rates(stats: Array) -> Dict[str, float]:
    s = stats_dict(stats)
    l1_acc = s["l1_hit"] + s["l1_miss"]
    l2_acc = s["l2_hit"] + s["l2_miss"]
    return {
        "l1_miss_rate": s["l1_miss"] / max(l1_acc, 1),
        "l2_miss_rate": s["l2_miss"] / max(l2_acc, 1),   # LLC (paper Fig. 5)
        "llc_mpki": 1000.0 * s["l2_miss"] / max(l1_acc, 1),
    }

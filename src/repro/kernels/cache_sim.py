"""Pallas TPU kernels: cache simulation over address traces.

This is the compute hot-spot of CXLRAMSim's vectorized re-think of gem5
(DESIGN.md §2): simulating a cache over a multi-million-access trace.  Two
kernels live here:

  * :func:`cache_sim` — the original single-level set-associative LRU cache
    (hit/miss trace), kept as the micro-benchmark kernel;
  * :func:`mesi_cache_sim` — the **full two-level MESI + tier state
    machine** of :mod:`repro.core.cache`: per-core L1 tag/state/LRU arrays,
    a shared inclusive L2 with directory sharer bitmasks and per-line
    backing target, and the (8 + 2*n_targets)-counter stats vector
    (per-target memory reads/writes) — everything VMEM-resident
    across the grid.  It is the `pallas` backend of the batched trace engine
    (:mod:`repro.core.engine`); the `lax.scan` model in `repro.core.cache`
    is its bitwise oracle.

The TPU-native design shared by both:

  * **state lives in VMEM** — int32 arrays, persistent across the
    sequential TPU grid; the MESI kernels keep it in lane-dense planes
    (:class:`Layout`, 688 KiB at the paper's Table-I host);
  * the **trace streams from HBM in chunks** via the BlockSpec index_map,
    one grid step per chunk (double-buffered by the Pallas pipeline); the
    MESI kernels read it as scalars from SMEM;
  * within a chunk the state machine is a `fori_loop` (trace order is a true
    dependency), but each iteration's tag compare / LRU victim select /
    directory probe is a vectorized op across `ways` lanes;
  * `mesi_cache_sim` adds a leading **batch grid dimension**: the engine
    stacks B configurations and the kernel re-initializes its VMEM state at
    each row's first chunk, so a whole multi-config sweep is one kernel
    launch.

`mesi_cache_sim`, `mesi_segment` and the epoch program's
`mesi_dyn_segment` compile for a TPU v5e (`tests/test_chip_compile.py`)
and are what the static and the epoch program run there by default.

Sentinel padding convention
---------------------------
Traces need not be a multiple of the chunk size: :func:`pad_trace` appends
entries with ``addr == SENTINEL`` (= -1; real line addresses are >= 0) and
zeros elsewhere.  Both kernel bodies gate *every* state write and stat
increment on ``addr >= 0``, so padded entries leave the tag stores, LRU
clocks, MESI states and stats untouched — stats over a padded trace are
bitwise-equal to the unpadded run, and no post-hoc stripping of stats is
needed (per-access outputs such as `hits` are simply sliced back to the
original length).  Padding must only be appended at the end of a trace:
logical time advances across sentinels, matching the reference scan.

Semantics match the pure-JAX references exactly (tested across geometry
sweeps in interpret mode; `interpret=False` is the TPU target).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cache import (
    L1_HIT, L1_MISS, L2_HIT, L2_MISS, MEM_READ,
    I, S, E, M, SENTINEL, CacheParams, CacheState,
    coherence_base, mem_write_base, nstats,
)
from repro.core.numa import LINES_PER_PAGE
from repro.core.tiering_dyn import encode_hot_key

Array = jax.Array


def pad_trace(chunk: int, addr: Array, *fields: Array) -> Tuple[Array, ...]:
    """Pad a trace to a multiple of `chunk` with sentinel entries.

    `addr` is padded with :data:`SENTINEL`; every extra field (is_write,
    core, tier, ...) with zeros.  Works on 1-D traces and (B, N) batches
    (padding along the last axis).  Returns the padded arrays.
    """
    n = addr.shape[-1]
    pad = (-n) % chunk
    if pad == 0:
        return (addr, *fields)
    widths = [(0, 0)] * (addr.ndim - 1) + [(0, pad)]
    out = [jnp.pad(addr.astype(jnp.int32), widths, constant_values=SENTINEL)]
    out += [jnp.pad(f.astype(jnp.int32), widths) for f in fields]
    return tuple(out)


# ---------------------------------------------------------------------------
# Single-level LRU kernel (micro-benchmark path)
# ---------------------------------------------------------------------------
def _cache_sim_kernel(addr_ref, hits_ref, tags_ref, use_ref,
                      tag_scratch, use_scratch, *, chunk: int,
                      n_sets: int, n_ways: int, n_chunks: int):
    step = pl.program_id(0)

    # initialize persistent VMEM state on the first grid step
    @pl.when(step == 0)
    def _init():
        tag_scratch[...] = jnp.full((n_sets, n_ways), -1, jnp.int32)
        use_scratch[...] = jnp.zeros((n_sets, n_ways), jnp.int32)

    base_t = step * chunk + 1

    def body(i, carry):
        a = addr_ref[i]
        valid = a >= 0                                 # sentinel padding
        s = jnp.where(valid, a, 0) & (n_sets - 1)
        row = tag_scratch[s, :]                        # (ways,) lanes
        hit_mask = row == a
        hit = jnp.any(hit_mask) & valid
        way = jnp.where(hit, jnp.argmax(hit_mask),
                        jnp.argmin(use_scratch[s, :])).astype(jnp.int32)
        tag_scratch[s, way] = jnp.where(valid, a, tag_scratch[s, way])
        use_scratch[s, way] = jnp.where(valid, base_t + i,
                                        use_scratch[s, way])
        hits_ref[i] = hit.astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, chunk, body, 0)

    # publish final state on the last grid step
    @pl.when(step == n_chunks - 1)
    def _out():
        tags_ref[...] = tag_scratch[...]
        use_ref[...] = use_scratch[...]


@functools.partial(jax.jit,
                   static_argnames=("n_sets", "n_ways", "chunk", "interpret"))
def cache_sim(addr: Array, *, n_sets: int, n_ways: int,
              chunk: int = 512, interpret: bool = True):
    """Run the single-level cache-simulation kernel.

    Args:
      addr: (N,) int32 cacheline-index trace; any length — automatically
        sentinel-padded to a multiple of `chunk` (see module docstring),
        padded entries never touch tags/LRU state.
      n_sets, n_ways: cache geometry (n_sets a power of two).
      chunk: trace elements per grid step (VMEM tile of the trace).
      interpret: run the kernel body in Python (CPU validation mode).

    Returns: (hits (N,) int32, tags (n_sets, n_ways) int32, use int32).
    """
    n = addr.shape[0]
    if n_sets & (n_sets - 1) != 0:
        raise ValueError(f"n_sets must be a power of two, got {n_sets}")
    (addr,) = pad_trace(chunk, addr)
    n_chunks = addr.shape[0] // chunk

    kernel = functools.partial(_cache_sim_kernel, chunk=chunk,
                               n_sets=n_sets, n_ways=n_ways,
                               n_chunks=n_chunks)
    hits, tags, use = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec((chunk,), lambda i: (i,))],
        out_specs=[
            pl.BlockSpec((chunk,), lambda i: (i,)),
            pl.BlockSpec((n_sets, n_ways), lambda i: (0, 0)),
            pl.BlockSpec((n_sets, n_ways), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((addr.shape[0],), jnp.int32),
            jax.ShapeDtypeStruct((n_sets, n_ways), jnp.int32),
            jax.ShapeDtypeStruct((n_sets, n_ways), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_sets, n_ways), jnp.int32),
            pltpu.VMEM((n_sets, n_ways), jnp.int32),
        ],
        interpret=interpret,
    )(addr.astype(jnp.int32))
    return hits[:n], tags, use


# ---------------------------------------------------------------------------
# Full two-level MESI + tier kernel (batched engine backend)
# ---------------------------------------------------------------------------
#: Lanes of the kernels' stats row: counter ``k`` of
#: :func:`repro.core.cache.stat_names` accumulates in lane ``k``.
STAT_LANES = 128
#: XLA lays a 1-D int32 array out on a TPU in tiles of this many elements;
#: a compiled kernel's trace block is a whole number of tiles.
TRACE_TILE = 1024
_NO_LANE = 1 << 30                   # above every lane index: "no match"
_INT_MAX = 2 ** 31 - 1
#: Per-slot output rows of the epoch kernel in one block: one sublane tile.
_SLOT_ROWS = 8


def _sets_per_row(sets: int, width: int) -> int:
    """Whole sets laid side by side in one plane row, up to 128 lanes."""
    g = 1
    while 2 * g <= sets and 2 * g * width <= 128:
        g *= 2
    return g


@dataclasses.dataclass(frozen=True)
class Layout:
    """Where the MESI kernels keep each cache line in their VMEM planes.

    Eight int32 planes hold the state, one field each: L1 tag, use and
    MESI state, then L2 tag, use, state, tier and sharers.  An L1 plane
    row holds ``g1`` whole sets side by side, each set as every core's
    ways (lane ``(set % g1) * cores * l1_ways + core * l1_ways + way``);
    an L2 plane row holds ``g2`` sets of ``l2_ways`` lanes.  ``g1`` and
    ``g2`` fill a row up to 128 lanes, so at the Table-I geometry the
    planes are (32, 128) and (256, 128): lane-dense, with no padding,
    and one row load reads every core's copies of a set.  The same lane
    of two planes is the same line, so a row's tag, use and state
    vectors line up with no shuffle.
    """
    cores: int
    l1_sets: int
    l1_ways: int
    l2_sets: int
    l2_ways: int

    @classmethod
    def of(cls, p: CacheParams) -> "Layout":
        return cls(p.cores, p.l1_sets, p.l1_ways, p.l2_sets, p.l2_ways)

    @property
    def cw(self) -> int:
        """Lanes of one L1 set: every core's ways."""
        return self.cores * self.l1_ways

    @property
    def g1(self) -> int:
        return _sets_per_row(self.l1_sets, self.cw)

    @property
    def g2(self) -> int:
        return _sets_per_row(self.l2_sets, self.l2_ways)

    @property
    def shape1(self) -> Tuple[int, int]:
        return (self.l1_sets // self.g1, self.g1 * self.cw)

    @property
    def shape2(self) -> Tuple[int, int]:
        return (self.l2_sets // self.g2, self.g2 * self.l2_ways)


#: VMEM of one TensorCore by device kind, as ``pltpu.get_tpu_info``
#: gives it, for a chip it cannot read: one that is only described, as
#: in the compile tests.
VMEM_BYTES = {"TPU v5 lite": 128 * 2 ** 20}
#: VMEM a MESI kernel asks for beyond its blocks, for Mosaic's internal
#: scratch.  The compiler for a described v5e accepts the blocks alone,
#: at 10 and 80 MiB, so this is headroom, not a measured need.
VMEM_MARGIN = 2 * 2 ** 20


def chip_vmem_bytes(device_kind: Optional[str] = None) -> int:
    """VMEM of one TensorCore: of ``device_kind`` from :data:`VMEM_BYTES`,
    else of the chip JAX runs on (``pltpu.get_tpu_info``, which reads the
    default device).  A kind it does not know raises."""
    if device_kind is None:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    if device_kind not in VMEM_BYTES:
        raise ValueError(f"no VMEM capacity known for {device_kind!r}")
    return VMEM_BYTES[device_kind]


def _tiled_bytes(shape: Tuple[int, int]) -> int:
    """VMEM of a 2-D int32 block, rows and lanes padded to (8, 128)."""
    return 4 * (-(-shape[0] // 8) * 8) * (-(-shape[1] // 128) * 128)


def _page_rows(n_pages: int) -> int:
    """Rows of a lane-dense per-page plane: page ``i`` sits in row
    ``i // 128``, lane ``i % 128``.  The rows fill whole (8, 128) tiles:
    on a v5e the carry-in copy of a plane that ends in part of a tile
    never completed."""
    return -(-n_pages // 1024) * 8


def vmem_bytes(params: CacheParams, *, n_pages: Optional[int] = None
               ) -> int:
    """VMEM that a MESI kernel's blocks take at this geometry.

    The blocks are one row's state: the stats row and the 8 state
    planes, each held in one buffer (its index does not change along a
    row's blocks).  :func:`mesi_cache_sim` and :func:`mesi_segment` take
    the same, since the segment kernel's carry-in stays in HBM.  0.68
    MiB at the paper's Table-I host, 10.05 MiB at an 8-core CCD with a
    32 MiB 16-way L3.

    ``n_pages`` counts instead the epoch kernel (:func:`mesi_dyn_segment`)
    over that many pages: besides, its page map, page counters and
    per-target page lines as lane-dense page planes, its two migration
    rows, and its double-buffered block of per-slot rows.
    """
    lay = Layout.of(params)
    total = (_tiled_bytes((1, STAT_LANES)) + 3 * _tiled_bytes(lay.shape1)
             + 5 * _tiled_bytes(lay.shape2))
    if n_pages is not None:
        page_plane = _tiled_bytes((_page_rows(n_pages), 128))
        total += ((2 + params.n_targets) * page_plane
                  + 2 * _tiled_bytes((1, STAT_LANES))
                  + 2 * _tiled_bytes((_SLOT_ROWS, STAT_LANES)))
    return total


def vmem_limit_bytes(params: CacheParams, *,
                     n_pages: Optional[int] = None) -> int:
    """The scoped VMEM a MESI kernel compiles with: its blocks
    (:func:`vmem_bytes`) and :data:`VMEM_MARGIN`."""
    return vmem_bytes(params, n_pages=n_pages) + VMEM_MARGIN


def fits_chip(params: CacheParams, device_kind: Optional[str] = None, *,
              n_pages: Optional[int] = None) -> bool:
    """Whether a MESI kernel compiles at this geometry: its scoped VMEM
    (:func:`vmem_limit_bytes`; the epoch kernel's with ``n_pages``)
    fits the chip's (:func:`chip_vmem_bytes`)."""
    return (vmem_limit_bytes(params, n_pages=n_pages)
            <= chip_vmem_bytes(device_kind))


def _carry_planes(lay: Layout, l1p: Array, l2p: Array):
    """Split the engine's packed carry into the kernels' 8 state planes.

    ``l1p`` is (B, cores, s1, w1, 3) [tag, use, state] and ``l2p`` is
    (B, s2, w2, 5) [tag, use, state, tier, sharers]; each plane comes out
    (B, *lay.shape1) or (B, *lay.shape2).
    """
    b = l1p.shape[0]
    l1 = [jnp.swapaxes(l1p[..., k], 1, 2).reshape((b,) + lay.shape1)
          for k in range(3)]
    l2 = [l2p[..., k].reshape((b,) + lay.shape2) for k in range(5)]
    return [x.astype(jnp.int32) for x in l1 + l2]


def _l1_field(lay: Layout, plane: Array) -> Array:
    """(B, *lay.shape1) plane -> (B, cores, s1, w1)."""
    b = plane.shape[0]
    return jnp.swapaxes(
        plane.reshape(b, lay.l1_sets, lay.cores, lay.l1_ways), 1, 2)


def _state_of(lay: Layout, planes) -> CacheState:
    """The 8 planes as a batched CacheState."""
    b = planes[0].shape[0]
    l1t, l1u, l1s = (_l1_field(lay, x) for x in planes[:3])
    l2t, l2u, l2s, l2tier, l2sh = (
        x.reshape(b, lay.l2_sets, lay.l2_ways) for x in planes[3:])
    return CacheState(l1_tag=l1t, l1_use=l1u, l1_state=l1s, l2_tag=l2t,
                      l2_use=l2u, l2_state=l2s, l2_tier=l2tier,
                      l2_sharers=l2sh)


def _pack_planes(lay: Layout, planes):
    """Inverse of :func:`_carry_planes`: 8 planes -> (l1p, l2p)."""
    st = _state_of(lay, planes)
    l1p = jnp.stack([st.l1_tag, st.l1_use, st.l1_state], axis=-1)
    l2p = jnp.stack([st.l2_tag, st.l2_use, st.l2_state, st.l2_tier,
                     st.l2_sharers], axis=-1)
    return l1p, l2p


def _first_lane(mask: Array, lane: Array) -> Array:
    """Lowest lane of a (1, L) row where ``mask`` holds, as (1, 1);
    ``_NO_LANE`` where none does.  The first-index tie rule of
    ``jnp.argmax``/``jnp.argmin``, as a min that Mosaic lowers."""
    return jnp.min(jnp.where(mask, lane, _NO_LANE), axis=1, keepdims=True)


def _pick(sel: Array, row: Array) -> Array:
    """The value of a (1, L) row in the one lane ``sel`` marks, as (1, 1)."""
    return jnp.sum(jnp.where(sel, row, 0), axis=1, keepdims=True)


def _row(ref, q):
    return ref[pl.ds(q, 1), :]


def _mesi_access(planes, stats: Array, a_raw, w_i, c, tr, t, stat_gate,
                 *, lay: Layout, n_targets: int) -> Array:
    """One MESI access against the VMEM-resident state planes.

    The shared per-access body of every MESI kernel in this module.
    ``planes`` are the eight refs of :class:`Layout`; the trace entry
    (``a_raw``, ``w_i``, ``c``, ``tr``) and the clock ``t`` are scalars.
    Returns ``stats``, the (1, STAT_LANES) counter row, advanced.  The
    update sequence mirrors `repro.core.cache._step` operation for
    operation, so stats and final state are bitwise-identical to the
    scan reference.

    Written for Mosaic: a lookup loads one plane row and masks its set's
    lanes; a way is chosen by a lane-index min (:func:`_first_lane`), a
    way's field read by a masked sum (:func:`_pick`), and a write is a
    masked store of the whole row.  The only scalar taken from vector
    data is the L2 set of the L1 victim's writeback.  The L2 victim's L1
    copies are found by one compare over the whole L1 plane (4,096 tags
    at Table I: four vregs a plane), which needs no index.

    ``stat_gate`` multiplies every stat increment (1 = measure, 0 =
    functional warming: the state machine still runs full fidelity, only
    the counters freeze) — the sampled-slot masking contract of
    :mod:`repro.core.sampling`; state writes are gated only on trace
    validity, exactly like the reference.
    """
    l1t, l1u, l1s, l2t, l2u, l2s, l2tier, l2sh = planes
    cw, w1, w2, g1, g2 = lay.cw, lay.l1_ways, lay.l2_ways, lay.g1, lay.g2
    sh1, sh2 = g1.bit_length() - 1, g2.bit_length() - 1
    w = w_i != 0
    valid = a_raw >= 0                    # sentinel padding gate
    vi = valid.astype(jnp.int32) * stat_gate
    a = jnp.where(valid, a_raw, 0)
    i32 = lambda x: x.astype(jnp.int32)
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, lay.shape1[1]), 1)
    lane2 = jax.lax.broadcasted_iota(jnp.int32, (1, lay.shape2[1]), 1)

    # ---------------- L1 lookup ----------------
    set1 = a & (lay.l1_sets - 1)
    q1, off1 = set1 >> sh1, (set1 & (g1 - 1)) * cw
    own_lo = off1 + c * w1
    row_t, row_u, row_s = _row(l1t, q1), _row(l1u, q1), _row(l1s, q1)
    own = (lane1 >= own_lo) & (lane1 < own_lo + w1)
    live = (row_t == a) & (row_s != I)
    hit_lane = _first_lane(own & live, lane1)
    l1_hit = hit_lane < _NO_LANE
    umin = jnp.min(jnp.where(own, row_u, _INT_MAX), axis=1, keepdims=True)
    sel1 = lane1 == jnp.where(
        l1_hit, hit_lane, _first_lane(own & (row_u == umin), lane1))
    cur_state = _pick(sel1, row_s)
    evict_tag = _pick(sel1, row_t)
    needs_upgrade = l1_hit & w & (cur_state == S)

    # directory-equivalent probe: every other core's copy of this line
    other = live & (lane1 >= off1) & (lane1 < off1 + cw) & ~own
    n_other = jnp.sum(i32(other), axis=1, keepdims=True)
    # invalidate other copies on any write (upgrade or RFO fill)
    l1s[pl.ds(q1, 1), :] = jnp.where(other & (w & valid), I, row_s)

    # ---------------- L1 victim writeback (on miss) ----------------
    evict_valid = (~l1_hit) & (cur_state != I)
    evict_dirty = evict_valid & (cur_state == M)
    eset2 = jnp.sum(jnp.where(sel1, row_t, 0)) & (lay.l2_sets - 1)
    eq2, eoff = eset2 >> sh2, (eset2 & (g2 - 1)) * w2
    eseg = (lane2 >= eoff) & (lane2 < eoff + w2)
    # inclusive L2: mark dirty there on dirty eviction, drop the sharer;
    # no lane is selected when the line is not in L2
    esel = lane2 == _first_lane(eseg & (_row(l2t, eq2) == evict_tag), lane2)
    erow_s, erow_sh = _row(l2s, eq2), _row(l2sh, eq2)
    l2s[pl.ds(eq2, 1), :] = jnp.where(esel & evict_dirty & valid, M, erow_s)
    l2sh[pl.ds(eq2, 1), :] = jnp.where(esel & evict_valid & valid,
                                       erow_sh & ~(jnp.int32(1) << c),
                                       erow_sh)

    # ---------------- L2 lookup (only meaningful on L1 miss) --------
    set2 = a & (lay.l2_sets - 1)
    q2, off2 = set2 >> sh2, (set2 & (g2 - 1)) * w2
    row2_t, row2_u = _row(l2t, q2), _row(l2u, q2)
    seg2 = (lane2 >= off2) & (lane2 < off2 + w2)
    hit2_lane = _first_lane(seg2 & (row2_t == a), lane2)
    l2_hit_raw = hit2_lane < _NO_LANE
    umin2 = jnp.min(jnp.where(seg2, row2_u, _INT_MAX), axis=1,
                    keepdims=True)
    sel2 = lane2 == jnp.where(
        l2_hit_raw, hit2_lane, _first_lane(seg2 & (row2_u == umin2), lane2))
    l2_hit = l2_hit_raw & (~l1_hit)
    l2_miss = (~l2_hit_raw) & (~l1_hit)

    # ---- L2 victim handling on fill: back-invalidate + writeback ----
    row2_s, row2_tier, row2_sh = (_row(l2s, q2), _row(l2tier, q2),
                                  _row(l2sh, q2))
    v_tag = _pick(sel2, row2_t)
    v_state = _pick(sel2, row2_s)
    v_tier = _pick(sel2, row2_tier)
    v_valid = l2_miss & (v_state != I) & (v_tag != a)
    # the victim's L1 set, matched over the whole L1 plane
    vset1 = v_tag & (lay.l1_sets - 1)
    voff = (vset1 & (g1 - 1)) * cw
    rows = jax.lax.broadcasted_iota(jnp.int32, lay.shape1, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, lay.shape1, 1)
    all_t, all_s = l1t[...], l1s[...]
    v_copies = ((rows == (vset1 >> sh1)) & (lanes >= voff)
                & (lanes < voff + cw) & (all_t == v_tag) & (all_s != I))
    v_l1_dirty = jnp.max(i32(v_copies & (all_s == M)), axis=(0, 1),
                         keepdims=True) > 0
    n_vcopies = jnp.sum(i32(v_copies), axis=(0, 1), keepdims=True)
    l1s[...] = jnp.where(v_copies & v_valid & valid, I, all_s)
    v_dirty = v_valid & ((v_state == M) | v_l1_dirty)

    # ---- install / update line in L2 ----
    fill2 = sel2 & l2_miss & valid
    me = jnp.int32(1) << c
    q2s = pl.ds(q2, 1)
    l2t[q2s, :] = jnp.where(fill2, a, row2_t)
    l2tier[q2s, :] = jnp.where(fill2, tr, row2_tier)
    l2s[q2s, :] = jnp.where(fill2, E, row2_s)
    l2u[q2s, :] = jnp.where(sel2 & (l2_hit | l2_miss) & valid, t, row2_u)
    l2sh[q2s, :] = jnp.where(
        fill2, me, jnp.where(sel2 & l2_hit & valid, row2_sh | me, row2_sh))

    # ---------------- install / update line in L1 ----------------
    sole = n_other == 0
    fill_state = jnp.where(w, M, jnp.where(sole, E, S))
    hit_state = jnp.where(w, M, cur_state)
    new_state = jnp.where(l1_hit, hit_state, fill_state)
    put1 = sel1 & valid
    q1s = pl.ds(q1, 1)
    l1t[q1s, :] = jnp.where(put1, a, row_t)
    l1u[q1s, :] = jnp.where(put1, t, row_u)
    l1s[q1s, :] = jnp.where(put1, new_state, _row(l1s, q1))

    # ---- stats: one row add, counter k in lane k ----
    sl = jax.lax.broadcasted_iota(jnp.int32, (1, STAT_LANES), 1)
    upg, inval, binval, wb1 = (coherence_base(n_targets) + k
                               for k in range(4))
    incs = ((L1_HIT, i32(l1_hit)), (L1_MISS, i32(~l1_hit)),
            (L2_HIT, i32(l2_hit)), (L2_MISS, i32(l2_miss)),
            (MEM_READ + tr, i32(l2_miss)),
            (mem_write_base(n_targets) + v_tier, i32(v_dirty)),
            (upg, i32(needs_upgrade)),
            (inval, jnp.where(w, n_other, 0)),
            (binval, jnp.where(v_valid, n_vcopies, 0)),
            (wb1, i32(evict_dirty)))
    inc = sum(jnp.where(sl == k, amount, 0) for k, amount in incs)
    return stats + inc * vi


def _run_chunk(trace, planes, stats, base_t, *, block: int, lay: Layout,
               n_targets: int) -> None:
    """Run one block of a row's accesses: a ``fori_loop`` over the SMEM
    trace block, the stats row carried in registers."""
    addr_ref, w_ref, core_ref, tier_ref = trace

    def body(i, acc):
        return _mesi_access(planes, acc, addr_ref[i], w_ref[i], core_ref[i],
                            tier_ref[i], base_t + i, jnp.int32(1), lay=lay,
                            n_targets=n_targets)

    stats[...] = jax.lax.fori_loop(0, block, body, stats[...])


def _mesi_kernel(addr_ref, w_ref, core_ref, tier_ref, stats, *planes,
                 block: int, lay: Layout, n_targets: int):
    """One (batch-row, block) grid step of the two-level MESI state machine.

    The state lives in the output blocks: their index depends on the row
    only, so they stay in VMEM across the row's blocks and are written
    back once, after its last.  This kernel owns the fresh-state
    initialization; the per-access body is :func:`_mesi_access`.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for ref, fill in zip(planes, (-1, 0, 0, -1, 0, 0, 0, 0)):
            ref[...] = jnp.full(ref.shape, fill, jnp.int32)
        stats[...] = jnp.zeros(stats.shape, jnp.int32)

    _run_chunk((addr_ref, w_ref, core_ref, tier_ref), planes, stats,
               j * block + 1, block=block, lay=lay, n_targets=n_targets)


def _trace_blocks(chunk: int, interpret: bool, addr, *fields):
    """Pad a (B, N) trace batch to whole blocks and flatten it for SMEM.

    The loop reads every trace field as a scalar, so a block is a 1-D
    SMEM window of one row's trace: ``chunk`` entries, rounded up to
    whole :data:`TRACE_TILE` tiles when compiled.  Returns the flat
    fields, their block spec, the block and the blocks per row.
    """
    block = chunk if interpret else -(-chunk // TRACE_TILE) * TRACE_TILE
    padded = pad_trace(block, addr, *fields)
    b, n = padded[0].shape
    n_blocks = n // block
    flat = [x.astype(jnp.int32).reshape(b * n) for x in padded]
    spec = pl.BlockSpec((block,), lambda b_, j: (b_ * n_blocks + j,),
                        memory_space=pltpu.SMEM)
    return flat, spec, block, n_blocks


def _lane_row(x: Array) -> Array:
    """(B, w) -> (B, 1, STAT_LANES): value ``k`` of a row in lane ``k``."""
    return jnp.zeros((x.shape[0], 1, STAT_LANES), jnp.int32).at[
        :, 0, :x.shape[1]].set(jnp.asarray(x, jnp.int32))


def _state_shapes(lay: Layout):
    """Block shapes of the stats row and the 8 state planes."""
    return [(1, STAT_LANES)] + [lay.shape1] * 3 + [lay.shape2] * 5


def _state_specs(b: int, shapes, *, squeeze: bool = True):
    """Block specs and (B, ...) array shapes of a row's state blocks.

    A block's index changes with the row only, so the pipeline keeps it
    in one buffer: a second would be used only while the previous row's
    state is written back.  ``squeeze=False`` keeps the row axis in the
    block, as (1, rows, lanes), the shape a DMA from HBM fills.
    """
    def spec(shape):
        return pl.BlockSpec((None if squeeze else 1,) + shape,
                            lambda b_, j: (b_, 0, 0),
                            pipeline_mode=pl.Buffered(1))
    return ([spec(s) for s in shapes],
            [jax.ShapeDtypeStruct((b,) + s, jnp.int32) for s in shapes])


@functools.partial(jax.jit,
                   static_argnames=("params", "chunk", "interpret"))
def mesi_cache_sim(addr: Array, is_write: Array, core: Array, tier: Array,
                   *, params: CacheParams, chunk: int = 512,
                   interpret: bool = True
                   ) -> Tuple[Array, CacheState]:
    """Two-level MESI + tier simulation of a (B, N) trace batch.

    The grid is (B, n_blocks): blocks stream sequentially per batch row
    and the VMEM-resident state re-initializes at each row's first block,
    so a whole multi-configuration sweep is a single kernel launch.

    VMEM: the state planes of one row, ``4 B * (3 * cores * l1_sets *
    l1_ways + 5 * l2_sets * l2_ways)`` (688 KiB at the paper's Table-I
    host: 4 cores, 64 KiB L1, 2 MiB L2; 10.05 MiB at an 8-core CCD with
    a 32 MiB L3; the planes are lane-dense, so nothing pads), held once
    in the output blocks.  The kernel compiles with that much scoped
    VMEM and :data:`VMEM_MARGIN` (:func:`vmem_limit_bytes`), and not
    with Mosaic's default of 16 MiB, so any geometry compiles whose
    state fits the chip's VMEM (:func:`fits_chip`; 128 MiB on a v5e).
    The trace blocks live in SMEM (4 fields x 1,024 entries x 4 B,
    double-buffered: 32 KiB).

    Args:
      addr: (B, N) int32 line addresses; `SENTINEL` (-1) marks padding
        (appended automatically up to a whole block).
      is_write/core/tier: (B, N) int32.
      params: cache geometry (static).
      chunk: trace elements per grid step (rounded up to whole
        :data:`TRACE_TILE` tiles when compiled).
      interpret: interpret mode (CPU validation; TPU target is False).

    Returns: (stats (B, nstats(params.n_targets)) int32, batched
    CacheState) — bitwise-equal to running
    `repro.core.cache.simulate_trace` per row on the unpadded traces.
    """
    if addr.ndim != 2:
        raise ValueError("mesi_cache_sim expects a (B, N) batch")
    b = addr.shape[0]
    lay = Layout.of(params)
    trace, trace_spec, block, n_blocks = _trace_blocks(
        chunk, interpret, addr, is_write, core, tier)
    out_specs, out_shape = _state_specs(b, _state_shapes(lay))
    kernel = functools.partial(_mesi_kernel, block=block, lay=lay,
                               n_targets=params.n_targets)
    stats, *planes = pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=[trace_spec] * 4,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(params)),
        interpret=interpret,
    )(*trace)
    return stats[:, 0, :nstats(params.n_targets)], _state_of(lay, planes)


# ---------------------------------------------------------------------------
# Carry-in / carry-out segment kernel (streaming + checkpoint/resume)
# ---------------------------------------------------------------------------
def _mesi_segment_kernel(addr_ref, w_ref, core_ref, tier_ref, t0_ref,
                         *refs, block: int, lay: Layout, n_targets: int):
    """Segment variant of :func:`_mesi_kernel`: state flows carry->carry.

    Instead of a fresh state at each row's first block, the incoming
    carry (stats, state planes, logical clock t0) seeds the output
    blocks, so a trace split into segments threads identical arithmetic
    through the carry — the resumable-stream contract of
    :func:`repro.core.engine.run_batch_segment`.  The carry-in stays in
    HBM, where it shares its buffer with the carry-out, and is copied
    into the output blocks once a row: VMEM holds one copy of the state.
    """
    carry_in, blocks = refs[:9], refs[9:]
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for dst, src in zip(blocks, carry_in):
            pltpu.sync_copy(src.at[pl.ds(b, 1)], dst)

    stats, *planes = (ref.at[0] for ref in blocks)
    _run_chunk((addr_ref, w_ref, core_ref, tier_ref), planes, stats,
               t0_ref[b] + j * block, block=block, lay=lay,
               n_targets=n_targets)


@functools.partial(jax.jit,
                   static_argnames=("params", "chunk", "interpret"))
def mesi_segment(carry, addr: Array, is_write: Array, core: Array,
                 tier: Array, *, params: CacheParams, chunk: int = 512,
                 interpret: bool = True):
    """Advance the engine's packed batch carry over one trace segment.

    The carry is exactly :func:`repro.core.engine.init_batch_carry`'s
    ``(l1p, l2p, stats, t)`` tuple — what the reference
    ``run_batch_segment`` threads between segments and what checkpoint/
    resume snapshots — so segments may alternate freely between this
    kernel and the reference scan with bitwise-identical results.  VMEM
    as :func:`mesi_cache_sim`: the carry-in stays in HBM, aliased to the
    carry-out, and is copied into the output blocks at each row's start.

    Args:
      carry: ``(l1p, l2p, stats, t)`` packed batch carry (leading B).
      addr: (B, N) int32 line addresses; any N — sentinel-padded to a
        whole block internally.  Padded entries never touch state, and
        the returned clock advances by the *unpadded* N, so internal
        padding is invisible in the carry.
      is_write/core/tier: (B, N) int32.
      params: cache geometry (static).
      chunk: trace elements per grid step (rounded up to whole
        :data:`TRACE_TILE` tiles when compiled).
      interpret: interpret mode (CPU validation; TPU target is False).

    Returns: the advanced ``(l1p, l2p, stats, t)`` carry.
    """
    l1p, l2p, stats, t = carry
    if addr.ndim != 2:
        raise ValueError("mesi_segment expects a (B, N) batch")
    b, n = addr.shape
    ns = nstats(params.n_targets)
    lay = Layout.of(params)
    trace, trace_spec, block, n_blocks = _trace_blocks(
        chunk, interpret, addr, is_write, core, tier)
    state_specs, out_shape = _state_specs(b, _state_shapes(lay),
                                          squeeze=False)
    kernel = functools.partial(_mesi_segment_kernel, block=block, lay=lay,
                               n_targets=params.n_targets)
    outs = pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=[trace_spec] * 4 + [pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(state_specs),
        out_specs=state_specs,
        out_shape=out_shape,
        # the carry-in (stats row, then planes) becomes the carry-out
        input_output_aliases={5 + k: k for k in range(len(state_specs))},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(params)),
        interpret=interpret,
    )(*trace, t.astype(jnp.int32).reshape(b), _lane_row(stats),
      *_carry_planes(lay, l1p, l2p))
    l1p_o, l2p_o = _pack_planes(lay, outs[1:])
    return (l1p_o, l2p_o, outs[0][:, 0, :ns], t + jnp.int32(n))


# ---------------------------------------------------------------------------
# Epoch-structured dynamic-tiering kernel (tiering / sampling backend)
# ---------------------------------------------------------------------------
#: Column order of the packed per-row scalar input of
#: :func:`mesi_dyn_segment`: the per-row scalars of
#: :func:`repro.core.tiering_dyn.run_dynamic_segment` followed by the two
#: scalar carry components (logical clock, epoch-slot index).
DYN_SCALARS = ("dyn_flag", "n_pages", "budget", "threshold", "period",
               "dram_cap", "ssd_tid", "cxl_cap", "s_warm", "s_meas",
               "s_per", "t0", "eidx0")
#: First lane of a per-slot output row past its stat snapshot: the four
#: :data:`repro.core.tiering_dyn.SLOT_FIELDS` counters, then the slot's
#: measurement flag.
SLOT_LANE = STAT_LANES - 5
#: A line's page is its address shifted right by this many bits (a
#: floor division by :data:`LINES_PER_PAGE`, a power of two).
_PAGE_SHIFT = LINES_PER_PAGE.bit_length() - 1
if LINES_PER_PAGE != 1 << _PAGE_SHIFT:
    raise ValueError(f"{LINES_PER_PAGE} lines a page is not a power of two")


def _top_keys(keys: Array, n, k_max: int) -> Array:
    """Mask of the ``n`` largest of a plane's keys, ``n <= k_max``.

    Valid keys are distinct and non-negative (:func:`repro.core.
    tiering_dyn.encode_hot_key`) and every other entry is -1, so the
    ``r``-th largest key is the maximum below the ``(r-1)``-th, and the
    pages whose key reaches the ``n``-th are exactly the ``n`` that
    ``lax.top_k`` picks.  The caller keeps ``n`` within the count of
    valid keys; ``n == 0`` selects nothing.
    """
    def body(r, carry):
        prev, kth = carry
        m = jnp.max(jnp.where(keys < prev, keys, -1), axis=(0, 1),
                    keepdims=True)
        return m, jnp.where(r < n, m, kth)

    top = jnp.full((1, 1), _INT_MAX, jnp.int32)
    _, kth = jax.lax.fori_loop(0, k_max, body, (top, top))
    return keys >= kth


def _mesi_dyn_kernel(addr_ref, w_ref, core_ref, tier_ref, sc_ref, ptl_ref,
                     *refs, block: int, n_blocks: int, slot_len: int,
                     lay: Layout, n_targets: int, n_p: int, k_max: int,
                     count_bound: int):
    """One (batch-row, trace-block) grid step of the dynamic tierer.

    Mirrors :func:`repro.core.tiering_dyn._slot_step` decision for
    decision.  A slot is ``n_blocks`` SMEM trace blocks.  Each access
    reads its page's intent from the page map, routes to DRAM or the
    precomputed CXL target (level-2 pages to the SSD target), runs
    :func:`_mesi_access` and counts the page; sampled rows gate every
    stat increment on the slot's measurement flag, which equals the
    reference's masking of the slot's stat delta since stats are
    integer adds.  After a slot's last block the kernel writes the
    slot's output row and, at an epoch boundary, runs the migration
    stages on the page planes, where :func:`_top_keys` selects the
    pages that ``lax.top_k`` would.

    The carry (stats row, 8 state planes, page map and counter planes,
    migration rows) lives in output blocks that stay in VMEM along a
    row; the carry-in stays in HBM, shares its buffer with the
    carry-out and is copied in at each row's first block.
    """
    carry_in, blocks = refs[:13], refs[13:26]
    slot_out, acc = refs[26:]
    b, g = pl.program_id(0), pl.program_id(1)

    @pl.when(g == 0)
    def _init():
        for dst, src in zip(blocks, carry_in):
            pltpu.sync_copy(src.at[pl.ds(b, 1)], dst)

    stats, *rest = (ref.at[0] for ref in blocks)
    planes, (pmap, counts, migr, migw) = rest[:8], rest[8:]
    (flag, npg, bud, thr, per, cap, ssd_t, l1cap, s_w, s_m, s_p, t0,
     eidx0) = (sc_ref[b * len(DYN_SCALARS) + k]
               for k in range(len(DYN_SCALARS)))
    lpp = jnp.int32(LINES_PER_PAGE)
    i32 = lambda x: x.astype(jnp.int32)
    slot, k = g // n_blocks, g % n_blocks
    eidx = eidx0 + slot                   # slot index entering this slot
    # sampled rows (s_p > 0): slots outside [s_w, s_w + s_m) of each
    # period functionally warm (state advances, counters freeze)
    pos = eidx % jnp.maximum(s_p, 1)
    meas = i32(jnp.where(s_p > 0, (pos >= s_w) & (pos < s_w + s_m), True))
    base_t = t0 + slot * slot_len + k * block
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, STAT_LANES), 1)

    def body(i, carry):
        acc_t, acc_d, st = carry
        a_raw = addr_ref[i]
        v = i32(a_raw >= 0)
        page = jnp.clip(a_raw >> _PAGE_SHIFT, 0, n_p - 1)
        q, at = pl.ds(page >> 7, 1), lane == (page & 127)
        intent = jnp.sum(jnp.where(at, pmap[q, :], 0))
        tr_s = tier_ref[i]
        # dynamic rows: page map decides DRAM vs the precomputed CXL
        # target (level-2 pages hit the SSD target instead); static
        # rows use the precomputed target verbatim
        tgt = jnp.where(flag != 0,
                        jnp.where(intent == 0, 0,
                                  jnp.where(intent >= 2, ssd_t, tr_s)),
                        tr_s)
        st = _mesi_access(planes, st, a_raw, w_ref[i], core_ref[i], tgt,
                          base_t + i, meas, lay=lay, n_targets=n_targets)
        counts[q, :] = counts[q, :] + jnp.where(at, v, 0)
        sel = jnp.where(flag != 0, intent, tgt)
        return acc_t + v, acc_d + v * i32(sel == 0), st

    zero = jnp.int32(0)
    acc_t, acc_d, stats[...] = jax.lax.fori_loop(
        0, block, body, (zero, zero, stats[...]))
    acc[0] = jnp.where(k == 0, 0, acc[0]) + acc_t
    acc[1] = jnp.where(k == 0, 0, acc[1]) + acc_d

    @pl.when(k == n_blocks - 1)
    def _slot_end():
        out = pl.ds(slot % _SLOT_ROWS, 1)

        def field(f, x):
            return jnp.where(lane == SLOT_LANE + f, x, 0)

        slot_out[out, :] = (stats[...] + field(0, acc[0]) + field(1, acc[1])
                            + field(4, meas))
        boundary = ((eidx + 1) % per) == 0
        shape = pmap.shape
        pid = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * 128
               + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        pvalid = pid < npg
        km = jnp.int32(k_max)

        def total(mask):
            return jnp.sum(i32(mask), axis=(0, 1), keepdims=True)

        def key(hotness, mask):
            return jnp.where(mask, encode_hot_key(hotness, pid, n_p), -1)

        def lines(mask):
            """(1, STAT_LANES) row: the pages' lines per target lane."""
            return sum(jnp.where(lane == t, jnp.sum(
                jnp.where(mask, ptl_ref[t], 0), axis=(0, 1),
                keepdims=True), 0) for t in range(n_targets))

        # ---- epoch-boundary promotion/demotion decision ----
        @pl.when(boundary & (bud > 0))
        def _migrate():
            pm, cn = pmap[...], counts[...]
            is_cxl = (pm == 1) & pvalid
            is_dram = (pm == 0) & pvalid
            hot = is_cxl & (cn >= thr)
            n_dram = total(is_dram)
            # closed-form counts of the reference's top-k mask sums
            # (every min the rank/validity masks imply, including the
            # top-k width itself)
            n_want = jnp.minimum(jnp.minimum(total(hot), bud), km)
            free = jnp.maximum(cap - n_dram, 0)
            n_dem_needed = jnp.clip(n_want - free, 0, bud)
            n_dem = jnp.minimum(jnp.minimum(n_dem_needed, n_dram), km)
            n_pro = jnp.minimum(jnp.minimum(n_want, free + n_dem), km)
            pro = _top_keys(key(cn, hot), n_pro, k_max)
            dem = _top_keys(key(count_bound - cn, is_dram), n_dem, k_max)
            pmap[...] = jnp.where(pro, 0, jnp.where(dem, 1, pm))
            # promotions read the page from its CXL endpoints + write it
            # to DRAM; demotions read DRAM + write the CXL endpoints
            migr[...] += lines(pro) + jnp.where(lane == 0, n_dem * lpp, 0)
            migw[...] += lines(dem) + jnp.where(lane == 0, n_pro * lpp, 0)
            slot_out[out, :] += field(2, n_pro) + field(3, n_dem)

            # ---- three-tier SSD stage (tiering_dyn._ssd_stage twin) ----
            @pl.when(ssd_t > 0)
            def _ssd():
                pm2 = pmap[...]
                hot2 = (pm2 == 2) & pvalid & (cn >= thr)
                n_sup = jnp.minimum(jnp.minimum(total(hot2), bud), km)
                sup = _top_keys(key(cn, hot2), n_sup, k_max)
                pm3 = jnp.where(sup, 1, pm2)
                is_l1 = (pm3 == 1) & pvalid
                n_l1 = total(is_l1)
                over = jnp.clip(n_l1 - l1cap, 0, bud)
                n_over = jnp.minimum(jnp.minimum(over, n_l1), km)
                down = _top_keys(key(count_bound - cn, is_l1), n_over, k_max)
                pmap[...] = jnp.where(down, 2, pm3)
                # SSD promotion reads the SSD target + writes the CXL
                # endpoints; SSD demotion the reverse
                migr[...] += lines(down) + jnp.where(lane == ssd_t,
                                                     n_sup * lpp, 0)
                migw[...] += lines(sup) + jnp.where(lane == ssd_t,
                                                    n_over * lpp, 0)
                slot_out[out, :] += field(2, n_sup) + field(3, n_over)

        @pl.when(boundary)
        def _reset():
            counts[...] = jnp.zeros(shape, jnp.int32)


@functools.partial(jax.jit, static_argnames=("params", "k_max",
                                             "count_bound", "chunk",
                                             "interpret"))
def mesi_dyn_segment(carry, addr: Array, is_write: Array, core: Array,
                     tier: Array, dyn_flag, n_pages, budget, threshold,
                     period, dram_cap, ssd_tid, cxl_cap,
                     page_target_lines, s_warm, s_meas,
                     s_per, *, params: CacheParams, k_max: int,
                     count_bound: int, chunk: int = TRACE_TILE,
                     interpret: bool = True):
    """Advance the batched epoch carry over a (B, E, slot_len) segment.

    The carry is exactly :func:`repro.core.tiering_dyn.init_dyn_carry`'s
    9-tuple and the scalar arguments follow
    :func:`repro.core.tiering_dyn.run_dynamic_segment`'s order, so the
    kernel drops into the dynamic-tiering segment loop (and the
    resilient executor's checkpointed replay) as a backend swap:
    segments may alternate freely between this kernel and the reference
    scan with bitwise-identical carries and per-slot outputs.

    The grid is (B, E * blocks per slot).  A slot streams as 1-D SMEM
    trace blocks of ``chunk`` entries (rounded up to whole
    :data:`TRACE_TILE` tiles when compiled), its tail padded with
    sentinels, which touch nothing.  The page map and page counters are
    lane-dense VMEM planes of 128 pages a row, in whole (8, 128) tiles,
    read and counted by row per access and reduced whole at an epoch
    boundary; the per-row scalars sit in SMEM.  VMEM holds one copy of
    the carry, as in
    :func:`mesi_segment`; the kernel compiles with its blocks' VMEM
    (:func:`vmem_limit_bytes` with ``n_pages``) and runs on a TPU where
    that fits the chip (:func:`fits_chip`).  The stat snapshot, slot
    counters and measurement flag of each slot come out as one
    128-lane row, so ``nstats(params.n_targets)`` may not pass
    :data:`SLOT_LANE`.

    Returns ``(carry, slots, snaps, meas)``: the advanced carry, the
    (B, E, 4) per-slot counters (:data:`repro.core.tiering_dyn.
    SLOT_FIELDS`), the (B, E, nstats) cumulative stat snapshots and the
    (B, E) measurement flags.
    """
    l1p, l2p, stats, t, pmap, counts, mig_rd, mig_wr, eidx = carry
    if addr.ndim != 3:
        raise ValueError("mesi_dyn_segment expects a (B, E, slot_len) batch")
    b, e, slot_len = addr.shape
    n_p = int(page_target_lines.shape[1])
    n_t = params.n_targets
    ns = nstats(n_t)
    if ns > SLOT_LANE:
        raise ValueError(f"{n_t} targets' {ns} counters leave no lanes for "
                         f"the per-slot fields (at most {SLOT_LANE})")
    lay = Layout.of(params)
    # k_max is a static argname — int() runs at trace time, not on a
    # traced value  # repro-lint: disable=RL201
    k_max = min(int(k_max), n_p)
    block = min(chunk, slot_len)
    if not interpret:
        block = -(-block // TRACE_TILE) * TRACE_TILE
    n_blocks = -(-slot_len // block)
    e_out = -(-e // _SLOT_ROWS) * _SLOT_ROWS
    rows = _page_rows(n_p)

    def i32(x):
        return jnp.asarray(x, jnp.int32)

    def page_plane(x):
        """(..., n_p) -> (..., rows, 128), padding pages never valid."""
        x = i32(x)
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, rows * 128 - n_p)])
        return x.reshape(x.shape[:-1] + (rows, 128))

    trace = [x.reshape(-1) for x in pad_trace(
        n_blocks * block, i32(addr), i32(is_write), i32(core), i32(tier))]
    sc = jnp.stack([i32(dyn_flag), i32(n_pages), i32(budget),
                    i32(threshold), i32(period), i32(dram_cap),
                    i32(ssd_tid), i32(cxl_cap),
                    i32(s_warm), i32(s_meas), i32(s_per),
                    i32(t), i32(eidx)], axis=1).reshape(-1)
    carry_in = [_lane_row(stats), *_carry_planes(lay, l1p, l2p),
                page_plane(pmap), page_plane(counts), _lane_row(mig_rd),
                _lane_row(mig_wr)]
    state_specs, state_shape = _state_specs(
        b, [x.shape[1:] for x in carry_in], squeeze=False)

    kernel = functools.partial(
        _mesi_dyn_kernel, block=block, n_blocks=n_blocks,
        slot_len=slot_len, lay=lay, n_targets=n_t, n_p=n_p, k_max=k_max,
        count_bound=count_bound)
    trace_spec = pl.BlockSpec((block,),
                              lambda b_, g: (b_ * e * n_blocks + g,),
                              memory_space=pltpu.SMEM)
    ptl_spec = pl.BlockSpec((None, n_t, rows, 128),
                            lambda b_, g: (b_, 0, 0, 0),
                            pipeline_mode=pl.Buffered(1))
    slot_spec = pl.BlockSpec((None, _SLOT_ROWS, STAT_LANES),
                             lambda b_, g: (b_, g // (n_blocks * _SLOT_ROWS),
                                            0))
    outs = pl.pallas_call(
        kernel,
        grid=(b, e * n_blocks),
        in_specs=[trace_spec] * 4 + [pl.BlockSpec(memory_space=pltpu.SMEM),
                                     ptl_spec]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(carry_in),
        out_specs=state_specs + [slot_spec],
        out_shape=state_shape + [
            jax.ShapeDtypeStruct((b, e_out, STAT_LANES), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        # the carry-in becomes the carry-out
        input_output_aliases={6 + k: k for k in range(len(carry_in))},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(params, n_pages=n_p)),
        interpret=interpret,
    )(*trace, sc, page_plane(jnp.swapaxes(i32(page_target_lines), 1, 2)),
      *carry_in)

    l1p_o, l2p_o = _pack_planes(lay, outs[1:9])
    pmap_o, counts_o = (x.reshape(b, rows * 128)[:, :n_p]
                        for x in outs[9:11])
    per_slot = outs[13][:, :e]
    new_carry = (l1p_o, l2p_o, outs[0][:, 0, :ns],
                 t + jnp.int32(e * slot_len), pmap_o, counts_o,
                 outs[11][:, 0, :n_t], outs[12][:, 0, :n_t],
                 eidx + jnp.int32(e))
    return (new_carry, per_slot[..., SLOT_LANE:SLOT_LANE + 4],
            per_slot[..., :ns], per_slot[..., SLOT_LANE + 4])

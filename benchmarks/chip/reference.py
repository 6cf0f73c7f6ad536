"""Plain reference of one cell's sweep: the rows it must return.

A straightforward implementation of the simulator's semantics, written
from its documented behaviour and sharing no code with it (nothing here
imports ``repro``):

- the workload traces (pointer chase, hot/cold, KV decode: the serving
  loop that records the KV trace is re-run here);
- page placement and the HDM interleave decode to a target;
- the two-level inclusive MESI hierarchy, one access at a time;
- epoch-based hot-page promotion (dynamic tiering);
- the Picard timing fixed point over the per-target queueing curves.

Everything is plain Python and NumPy on the host.  ``sweep_rows`` takes
the configuration and traffic files as the harness loads them, and a
float type: ``numpy.float64`` is the reference, ``numpy.float32`` the
control, which must come out as not correct.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

I, S, E, M = 0, 1, 2, 3          # MESI states
DRAM, CXL = 0, 1                 # page intents
MASK32 = 0xFFFFFFFF

# Fixed by the specifications the simulator models, not by a deployment:
# 4 KiB pages, a 256 B HDM interleave granularity, PCIe payload GB/s per
# lane by generation, and the CXL 2.0 68 B flit (16 B slots plus CRC, so
# 17 wire bytes a slot; one header and four data slots carry a line).
PAGE_BYTES = 4096
HDM_GRANULARITY_BYTES = 256
PCIE_GBPS_PER_LANE = {5: 3.938}
SLOT_WIRE_BYTES, HEADER_SLOTS, DATA_SLOTS = 17, 1, 4


# ---------------------------------------------------------------------------
# Traces: (addr, is_write, tier or None, n_pages)
# ---------------------------------------------------------------------------
def _mix32_int(x: int) -> int:
    x &= MASK32
    x = (x * 0x9E3779B1) & MASK32
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & MASK32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & MASK32
    return x ^ (x >> 16)


def _mix32(ctr: np.ndarray, seed: int) -> np.ndarray:
    x = (ctr.astype(np.uint64) ^ np.uint64(seed & MASK32))
    x = (x * np.uint64(0x9E3779B1)) & np.uint64(MASK32)
    x = ((x ^ (x >> np.uint64(16))) * np.uint64(0x85EBCA6B)) & np.uint64(MASK32)
    x = ((x ^ (x >> np.uint64(13))) * np.uint64(0xC2B2AE35)) & np.uint64(MASK32)
    return x ^ (x >> np.uint64(16))


def _pages(n_lines: int, lines_per_page: int) -> int:
    return max(-(-n_lines // lines_per_page), 1)


def pointer_chase(fp: int, seed: int, hops_per_line: int, geo) -> tuple:
    """Dependent loads around a full-period affine ring of cachelines."""
    n = max(fp // geo["line_bytes"], 2)
    rad, x, d = 1, n, 2
    while d * d <= x:
        if x % d == 0:
            rad *= d
            while x % d == 0:
                x //= d
        d += 1
    if x > 1:
        rad *= x
    if n % 4 == 0 and rad % 4 != 0:
        rad *= 2
    a = (rad + 1) % n
    c = _mix32_int(seed) % n
    while math.gcd(c, n) != 1:
        c = (c + 1) % n
    pos = _mix32_int(seed ^ 0x5BF03635) % n
    addr = np.empty(hops_per_line * n, np.int64)
    for t in range(addr.shape[0]):
        addr[t] = pos
        pos = (pos * a + c) % n
    return (addr, np.zeros(addr.shape[0], np.int64), None,
            _pages(n, geo["lines_per_page"]))


def hot_cold(fp: int, seed: int, hot_page_frac: float,
             hot_access_frac: float, accesses_per_line: int, geo) -> tuple:
    """A scattered hot page set takes `hot_access_frac` of the accesses."""
    lpp = geo["lines_per_page"]
    n_lines = max(fp // geo["line_bytes"], 2)
    n_pages = _pages(n_lines, lpp)
    n_hot = max(1, int(n_pages * hot_page_frac))
    stride = max(n_pages // n_hot, 1)
    hot_pages = (np.arange(n_hot, dtype=np.int64) * stride + stride // 2) \
        % n_pages
    ctr = np.arange(accesses_per_line * n_lines, dtype=np.uint64)
    gate = _mix32(ctr, seed)
    pick = _mix32(ctr, seed ^ 0x9E3779B9)
    off = _mix32(ctr, seed ^ 0x7F4A7C15)
    to_hot = (gate % 1024) < int(hot_access_frac * 1024)
    hot_line = (hot_pages[(pick % np.uint64(n_hot)).astype(np.int64)] * lpp
                + (off % np.uint64(lpp)).astype(np.int64))
    cold_line = (pick % np.uint64(n_lines)).astype(np.int64)
    addr = np.clip(np.where(to_hot, hot_line, cold_line), 0, n_lines - 1)
    is_write = (((off >> np.uint64(8)) % np.uint64(4)) == 0).astype(np.int64)
    return addr.astype(np.int64), is_write, None, n_pages


class _KVPool:
    """Paged KV pool: block tables, an HBM page budget, LRU demotion on
    allocation and promotion on a gather while HBM has room."""

    def __init__(self, n_pages: int, page_size: int, hbm_budget: int):
        self.page_size = page_size
        self.hbm_budget = hbm_budget
        self.free = list(range(n_pages))
        self.tier = [DRAM] * n_pages
        self.last_use = [0] * n_pages
        self.tables: Dict[int, List[int]] = {}
        self.lens: Dict[int, int] = {}
        self.clock = 0

    def _hbm_used(self) -> List[int]:
        return [p for t in self.tables.values() for p in t
                if self.tier[p] == DRAM]

    def allocate(self, sid: int) -> None:
        self.tables[sid] = []
        self.lens[sid] = 0

    def release(self, sid: int) -> None:
        self.free.extend(self.tables.pop(sid, []))
        self.lens.pop(sid, None)

    def append(self, sid: int, n: int) -> None:
        table, pos = self.tables[sid], self.lens[sid]
        self.clock += 1
        for i in range(n):
            blk = (pos + i) // self.page_size
            if blk >= len(table):
                if not self.free:
                    raise MemoryError
                page = self.free.pop()
                table.append(page)
                self.tier[page] = DRAM
                while True:
                    used = self._hbm_used()
                    if len(used) <= self.hbm_budget:
                        break
                    victim = min(used, key=lambda p: self.last_use[p])
                    self.tier[victim] = CXL
            self.last_use[table[blk]] = self.clock
        self.lens[sid] = pos + n

    def gather(self, sids: Sequence[int]) -> None:
        self.clock += 1
        for sid in sids:
            for page in self.tables[sid]:
                self.last_use[page] = self.clock
                if (self.tier[page] == CXL
                        and len(self._hbm_used()) < self.hbm_budget):
                    self.tier[page] = DRAM


def kv_decode(fp: int, seed: int, params, geo) -> tuple:
    """Decode-step KV page gathers and appends of a continuous batcher."""
    page_size = params["page_size"]
    page_bytes = page_size * params["kv_heads"] * params["head_dim"] * 2 * 2
    lpp_kv = max(page_bytes // geo["line_bytes"], 1)
    pool = max(4, min(fp // page_bytes, params["max_pool_pages"]))
    kv = _KVPool(pool, page_size, max(1, int(pool * params["hbm_fraction"])))
    token_bytes = max(page_bytes // page_size, 1)
    n_req = params["n_requests"]
    rng = np.random.default_rng(seed)
    pool_tokens = pool * page_size
    offered = min((fp // page_bytes) * page_size, 2 * pool_tokens)
    budget = max(offered // (n_req + 2), 2 * page_size)
    cap = max(pool_tokens // 2, page_size + 1)
    waiting = []                       # [rid, prompt, new, generated]
    for rid in range(n_req):
        prompt = int(rng.integers(budget // 2, budget + 1))
        new = int(rng.integers(budget // 4 + 1, budget // 2 + 1))
        if prompt + new > cap:
            prompt = max(1, cap - new)
        waiting.append([rid, prompt, new, 0])
    running: list = []
    steps = []

    def preempt() -> None:
        # every request arrived before the first step, so the "youngest"
        # is the first running one
        if not running:
            raise MemoryError("KV pool exhausted with nothing to preempt")
        victim = running.pop(0)
        kv.release(victim[0])
        victim[3] = 0
        waiting.insert(0, victim)

    for _ in range(params["max_steps"]):
        if not (waiting or running):
            break
        if (waiting and len(running) < params["max_running"]
                and -(-(waiting[0][1] + waiting[0][2]) // page_size)
                <= len(kv.free)):
            req = waiting.pop(0)
            kv.allocate(req[0])
            running.append(req)
            try:
                kv.append(req[0], req[1])
            except MemoryError:
                running.remove(req)
                kv.release(req[0])
                waiting.insert(0, req)
                preempt()
            continue
        if not running:
            continue
        sids = [r[0] for r in running]
        tier_now = list(kv.tier)
        reads = [p for sid in sids for p in kv.tables[sid]]
        read_tiers = [int(tier_now[p] == CXL) for p in reads]
        kv.gather(sids)
        writes = []
        try:
            for sid in sids:
                kv.append(sid, 1)
                pos = kv.lens[sid] - 1
                page = kv.tables[sid][pos // page_size]
                off = min((pos % page_size) * token_bytes
                          // geo["line_bytes"], lpp_kv - 1)
                writes.append((page, off, int(kv.tier[page] == CXL)))
        except MemoryError:
            preempt()
            continue
        steps.append((reads, read_tiers, writes))
        for r in list(running):
            r[3] += 1
            if r[3] >= r[2]:
                running.remove(r)
                kv.release(r[0])
    addr, is_write, tier = [], [], []
    for reads, read_tiers, writes in steps:
        for p, t in zip(reads, read_tiers):
            addr.extend(range(p * lpp_kv, (p + 1) * lpp_kv))
            is_write.extend([0] * lpp_kv)
            tier.extend([t] * lpp_kv)
        for p, off, t in writes:
            addr.append(p * lpp_kv + off)
            is_write.append(1)
            tier.append(t)
    return (np.asarray(addr, np.int64), np.asarray(is_write, np.int64),
            np.asarray(tier, np.int64),
            _pages(pool * lpp_kv, geo["lines_per_page"]))


WORKLOADS = {
    # kind -> (row label, dependent loads, generator)
    "PointerChase": ("pointer_chase", True, lambda fp, s, p, g: pointer_chase(
        fp, s, p.get("hops_per_line", 2), g)),
    "HotCold": ("hot_cold", False, lambda fp, s, p, g: hot_cold(
        fp, s, p.get("hot_page_frac", 0.125), p.get("hot_access_frac", 0.9),
        p.get("accesses_per_line", 4), g)),
    "KVDecode": ("kv_decode", False, lambda fp, s, p, g: kv_decode(
        fp, s, p, g)),
}


# ---------------------------------------------------------------------------
# Placement and routing
# ---------------------------------------------------------------------------
def policy_label(pol) -> str:
    if pol["kind"] == "ZNuma":
        return f"znuma(cxl={pol['cxl_fraction']:.0%})"
    return f"interleave({pol['dram_weight']}:{pol['cxl_weight']})"


def policy_pages(pol, n_pages: int) -> np.ndarray:
    """Page -> intent (DRAM or CXL) under a placement policy."""
    page = np.arange(n_pages)
    if pol["kind"] == "ZNuma":
        n_dram = int(round(n_pages * (1.0 - pol["cxl_fraction"])))
        return (page >= n_dram).astype(np.int64)
    if pol["kind"] == "WeightedInterleave":
        period = pol["dram_weight"] + pol["cxl_weight"]
        return (page % period >= pol["dram_weight"]).astype(np.int64)
    raise ValueError(f"the reference has no policy {pol['kind']!r}")


def cxl_target(line: np.ndarray, topo) -> np.ndarray:
    """Expander (target 1..K) of each line under the K-way HDM decode."""
    g_lines = HDM_GRANULARITY_BYTES // 64
    return 1 + (line // g_lines) % topo["expanders"]


def first_touch(tier: np.ndarray, addr: np.ndarray, n_pages: int,
                lpp: int) -> np.ndarray:
    pmap = np.full(n_pages, CXL, np.int64)
    seen = np.zeros(n_pages, bool)
    for a, t in zip(addr.tolist(), tier.tolist()):
        p = min(a // lpp, n_pages - 1)
        if not seen[p]:
            seen[p] = True
            pmap[p] = min(t, 2)
    return pmap


def dynamic_targets(addr, cxl_t, pmap, n_pages, tr, slot: int, lpp: int,
                    n_targets: int):
    """Epoch loop: route by the page map, promote the hottest CXL pages
    at each boundary.  Returns (targets, slots, mig_read, mig_write)."""
    pmap = pmap.copy()
    n = addr.shape[0]
    n_slots = -(-n // slot)
    period = tr["epoch_len"] // slot
    budget, thr = tr["budget"], tr["threshold"]
    cap = tr.get("dram_capacity_pages")
    cap = float("inf") if cap is None else cap
    page = np.minimum(addr // lpp, n_pages - 1)
    ptl = np.zeros((n_pages, n_targets), np.int64)
    lines = np.arange(n_pages * lpp)
    np.add.at(ptl, (lines // lpp, cxl_t(lines)), 1)
    target = np.zeros(n, np.int64)
    counts = np.zeros(n_pages, np.int64)
    slots = np.zeros((n_slots, 4), np.int64)
    mig_rd = np.zeros(n_targets, np.int64)
    mig_wr = np.zeros(n_targets, np.int64)
    for e in range(n_slots):
        sl = slice(e * slot, min((e + 1) * slot, n))
        intent = pmap[page[sl]]
        target[sl] = np.where(intent == DRAM, 0, cxl_t(addr[sl]))
        slots[e, 0] = intent.shape[0]
        slots[e, 1] = int((intent == DRAM).sum())
        np.add.at(counts, page[sl], 1)
        if (e + 1) % period:
            continue
        if budget > 0:
            hot = sorted((p for p in range(n_pages)
                          if pmap[p] == CXL and counts[p] >= thr),
                         key=lambda p: (-counts[p], p))
            dram = sorted((p for p in range(n_pages) if pmap[p] == DRAM),
                          key=lambda p: (counts[p], p))
            free = max(cap - len(dram), 0)
            n_dem = min(max(min(len(hot), budget) - free, 0), budget,
                        len(dram))
            n_pro = min(len(hot), budget, free + n_dem)
            for p in hot[:n_pro]:
                pmap[p] = DRAM
                mig_rd += ptl[p]
                mig_wr[0] += lpp
            for p in dram[:n_dem]:
                pmap[p] = CXL
                mig_rd[0] += lpp
                mig_wr += ptl[p]
            slots[e, 2], slots[e, 3] = n_pro, n_dem
        counts[:] = 0
    return target, slots, mig_rd, mig_wr


def tiering_label(tr) -> str:
    if tr is None:
        return "static"
    cap = tr.get("dram_capacity_pages")
    return (f"tpp(e={tr['epoch_len']},k={tr['budget']},t={tr['threshold']}"
            f"{'' if cap is None else f',cap={cap}'})")


def epoch_fractions(slots: np.ndarray, period: int) -> List[float]:
    out, last = [], -1
    for s in range(0, slots.shape[0], period):
        tot = int(slots[s:s + period, 0].sum())
        if tot:
            last = len(out)
        out.append(float(slots[s:s + period, 1].sum()) / tot if tot else 0.0)
    return out[:last + 1]


# ---------------------------------------------------------------------------
# The MESI hierarchy
# ---------------------------------------------------------------------------
def stat_names(n_targets: int) -> List[str]:
    if n_targets == 2:
        mem = ["mem_read_dram", "mem_read_cxl", "mem_write_dram",
               "mem_write_cxl"]
    else:
        cxl = [f"cxl{k}" for k in range(n_targets - 1)]
        mem = (["mem_read_dram"] + [f"mem_read_{c}" for c in cxl]
               + ["mem_write_dram"] + [f"mem_write_{c}" for c in cxl])
    return (["l1_hit", "l1_miss", "l2_hit", "l2_miss"] + mem
            + ["upgrades", "invalidations", "back_invalidations",
               "writebacks_l1"])


def mesi(cache, addr, is_write, target, n_targets: int) -> List[int]:
    """Counters of one trace through private L1s and an inclusive shared
    L2 with a sharer directory; LRU by last use, the lowest way first on
    ties.  Every access issues from core 0, as the sweep's do."""
    line = cache["line_bytes"]
    cores = cache["cores"]
    w1, w2 = cache["l1_ways"], cache["l2_ways"]
    s1n = cache["l1_bytes"] // (w1 * line)
    s2n = cache["l2_bytes"] // (w2 * line)
    m1, m2 = s1n - 1, s2n - 1
    l1t = [[[-1] * w1 for _ in range(s1n)] for _ in range(cores)]
    l1u = [[[0] * w1 for _ in range(s1n)] for _ in range(cores)]
    l1s = [[[I] * w1 for _ in range(s1n)] for _ in range(cores)]
    l2t = [[-1] * w2 for _ in range(s2n)]
    l2u = [[0] * w2 for _ in range(s2n)]
    l2s = [[I] * w2 for _ in range(s2n)]
    l2r = [[0] * w2 for _ in range(s2n)]      # backing target of the line
    l2d = [[0] * w2 for _ in range(s2n)]      # sharer bitmask
    l1_hit = l1_miss = l2_hit = l2_miss = 0
    upg = inval = binval = wb1 = 0
    reads = [0] * n_targets
    writes = [0] * n_targets
    core = 0
    me = 1 << core
    others = [c for c in range(cores) if c != core]
    t = 1
    for a, w, tgt in zip(addr.tolist(), is_write.tolist(), target.tolist()):
        s1 = a & m1
        tags, uses, sts = l1t[core][s1], l1u[core][s1], l1s[core][s1]
        way1 = -1
        if a in tags:
            for k in range(w1):
                if tags[k] == a and sts[k] != I:
                    way1 = k
                    break
        hit = way1 >= 0
        if not hit:
            way1 = uses.index(min(uses))
        cur = sts[way1]
        n_other = 0
        for c in others:
            ot, os_ = l1t[c][s1], l1s[c][s1]
            if a in ot:
                for k in range(w1):
                    if ot[k] == a and os_[k] != I:
                        n_other += 1
                        if w:
                            os_[k] = I
        if hit:
            l1_hit += 1
            if w and cur == S:
                upg += 1
        else:
            l1_miss += 1
            if cur != I:                      # L1 victim leaves
                vt = tags[way1]
                es = vt & m2
                if vt in l2t[es]:
                    ew = l2t[es].index(vt)
                    if cur == M:
                        wb1 += 1
                        l2s[es][ew] = M
                    l2d[es][ew] &= ~me
                elif cur == M:
                    wb1 += 1
        if w:
            inval += n_other
        if not hit:
            s2 = a & m2
            row = l2t[s2]
            if a in row:
                way2 = row.index(a)
                l2_hit += 1
                l2u[s2][way2] = t
                l2d[s2][way2] |= me
            else:
                l2_miss += 1
                way2 = l2u[s2].index(min(l2u[s2]))
                vtag, vst = row[way2], l2s[s2][way2]
                if vst != I:                  # L2 victim: back-invalidate
                    dirty = vst == M
                    vs1 = vtag & m1
                    for c in range(cores):
                        ct, cs = l1t[c][vs1], l1s[c][vs1]
                        if vtag in ct:
                            for k in range(w1):
                                if ct[k] == vtag and cs[k] != I:
                                    binval += 1
                                    dirty |= cs[k] == M
                                    cs[k] = I
                    if dirty:
                        writes[l2r[s2][way2]] += 1
                reads[tgt] += 1
                row[way2] = a
                l2u[s2][way2] = t
                l2s[s2][way2] = E
                l2r[s2][way2] = tgt
                l2d[s2][way2] = me
            new = M if w else (E if n_other == 0 else S)
        else:
            new = M if w else cur
        tags[way1], uses[way1], sts[way1] = a, t, new
        t += 1
    return ([l1_hit, l1_miss, l2_hit, l2_miss] + reads + writes
            + [upg, inval, binval, wb1])


# ---------------------------------------------------------------------------
# Timing fixed point
# ---------------------------------------------------------------------------
def _targets(cfg, ft):
    """Per-target timing: kind, idle and service ns, payload GB/s, group."""
    tm, topo = cfg["timing"], cfg["topology"]
    lane = ft(PCIE_GBPS_PER_LANE[tm["cxl"]["pcie_gen"]])
    eff = ft(64) / ft((HEADER_SLOTS + DATA_SLOTS) * SLOT_WIRE_BYTES)
    dram = tm["dram"]
    out = [dict(kind="dram", idle=ft(dram["idle_ns"]),
                service=ft(dram["service_ns"]),
                peak=ft(dram["channels"]) * ft(dram["channel_gbps"]),
                group=-1)]
    cx = tm["cxl"]
    link, service, backend = (ft(cx["link_prop_ns"]), ft(cx["service_ns"]),
                              ft(cx["backend_gbps"]))
    wire = ft(cx["lanes"]) * lane
    device = min(wire * eff, backend)
    group_pay = ft(0)
    if topo["kind"] == "switched":
        sw = topo["switch"]
        usp_lane = ft(PCIE_GBPS_PER_LANE[sw["usp_pcie_gen"]])
        group_pay = min(ft(sw["usp_lanes"]) * usp_lane * eff, ft(1e9))
        link = link + ft(2) * ft(sw["hop_ns"])
        backend = min(backend, group_pay / ft(max(topo["expanders"], 1)))
        service = service + ft(sw["service_ns"])
    one_way = ft(cx["packetize_ns"]) + link + ft(cx["depacketize_ns"])
    idle = ft(2) * one_way + ft(cx["backend_ns"]) + ft(dram["idle_ns"]) / ft(2)
    payload = min(wire * eff, backend)
    for _ in range(topo["expanders"]):
        out.append(dict(kind="cxl", idle=idle, service=service,
                        peak=payload, group=0 if group_pay else -1,
                        group_payload=group_pay,
                        device_payload=min(device, group_pay)))
    return out


def _queue(idle, service, rho, ft):
    rho = max(min(rho, ft(0.98)), ft(0))
    return idle + service * rho / (ft(2) * (ft(1) - rho))


def time_row(stats: List[int], mig_rd, mig_wr, targets, cpu, mlp: int, ft):
    """Closed fixed point of one row: (time, per-target bw, lat, mig bw)."""
    n_t = len(targets)
    n_acc = stats[0] + stats[1]
    reads = [ft(stats[4 + k]) + ft(mig_rd[k]) for k in range(n_t)]
    writes = [ft(stats[4 + n_t + k]) + ft(mig_wr[k]) for k in range(n_t)]
    lines = [reads[k] + writes[k] for k in range(n_t)]
    line_b = ft(64)
    nbytes = [v * line_b for v in lines]
    mlp = ft(mlp)
    base = (ft(n_acc) / (ft(cpu["ipc_core"]) * ft(cpu["freq_ghz"]))
            + ft(stats[2]) * ft(cpu["l2_hit_ns"]) / mlp)
    one = ft(1)
    t = max(base, one)
    lat = [tg["idle"] for tg in targets]
    grouped = [k for k in range(n_t) if targets[k]["group"] >= 0]
    gbytes = sum((nbytes[k] for k in grouped), ft(0))
    for _ in range(8):
        offered = [nbytes[k] / max(t, one) for k in range(n_t)]
        goff = sum((offered[k] for k in grouped), ft(0))
        stall, glat, gbw = ft(0), ft(0), ft(0)
        for k, tg in enumerate(targets):
            if lines[k] <= 0:
                continue
            rf = reads[k] / max(lines[k], one)
            if tg["group"] >= 0:
                lat[k] = _queue(tg["idle"], tg["service"],
                                goff / tg["group_payload"], ft)
                glat = glat + lines[k] * lat[k] / mlp
                gbw = max(gbw, nbytes[k] / tg["device_payload"])
                continue
            peak = (tg["peak"] if tg["kind"] == "dram"
                    else rf * tg["peak"] + (one - rf) * tg["peak"])
            lat[k] = _queue(tg["idle"], tg["service"], offered[k] / peak, ft)
            stall = stall + max(lines[k] * lat[k] / mlp, nbytes[k] / peak)
        if gbytes > 0:
            pay = targets[grouped[0]]["group_payload"]
            stall = stall + max(glat, max(gbytes / pay, gbw))
        t_new = base + stall
        converged = abs(t_new - t) / max(t, one) < ft(1e-6)
        t = t_new
        if converged:
            break
    bw = [nbytes[k] / max(t, one) for k in range(n_t)]
    mig = sum((ft(v) for v in list(mig_rd) + list(mig_wr)), ft(0)) * line_b
    return (t if n_acc > 0 else ft(0)), bw, lat, lines, mig / max(t, one)


# ---------------------------------------------------------------------------
# The sweep
# ---------------------------------------------------------------------------
def sweep_rows(cfg, traffic, seed: int, ft=np.float64) -> List[Dict]:
    """Rows of ``sim.sweep(**grid)`` for this cell, in the program's order:
    tiering, then footprint, then policy (one topology, one workload)."""
    cache, topo, cpu = cfg["cache"], cfg["topology"], cfg["cpu"]
    geo = {"line_bytes": cache["line_bytes"],
           "lines_per_page": PAGE_BYTES // cache["line_bytes"]}
    lpp = geo["lines_per_page"]
    wl = traffic["workload"]
    label, serial, gen = WORKLOADS[wl["kind"]]
    params = dict(wl.get("params", {}), **traffic.get("reference", {}))
    tierings = traffic.get("tiering") or []
    dynamic = [tr for tr in tierings if tr is not None]
    slot = math.gcd(*[tr["epoch_len"] for tr in dynamic]) if dynamic else 0
    n_t = 1 + topo["expanders"]
    targets = _targets(cfg, ft)
    mlp = 1 if serial else (1 if cpu["kind"] == "inorder" else cpu["mlp"])
    names = stat_names(n_t)
    tlabels = (["dram", "cxl"] if n_t == 2
               else ["dram"] + [f"cxl{k}" for k in range(n_t - 1)])
    topo_name = (f"direct{topo['expanders']}" if topo["kind"] == "direct"
                 else f"switch{topo['expanders']}")
    cxl_t = lambda line: cxl_target(line, topo)  # noqa: E731
    rows = []
    for tr in (tierings or [None]):
        for k in traffic["footprint_factors"]:
            addr, is_write, tier, n_pages = gen(k * cache["l2_bytes"], seed,
                                                params, geo)
            for pol in traffic["policies"]:
                mig_rd = mig_wr = np.zeros(n_t, np.int64)
                frac = None
                migrated = 0
                if tr is None:
                    intent = (tier if tier is not None else policy_pages(
                        pol, n_pages)[np.minimum(addr // lpp, n_pages - 1)])
                    target = np.where(intent == DRAM, 0, cxl_t(addr))
                else:
                    pmap0 = (first_touch(tier, addr, n_pages, lpp)
                             if tier is not None
                             else policy_pages(pol, n_pages))
                    target, slots, mig_rd, mig_wr = dynamic_targets(
                        addr, cxl_t, pmap0, n_pages, tr, slot, lpp, n_t)
                    frac = epoch_fractions(slots, tr["epoch_len"] // slot)
                    migrated = int(slots[:, 2].sum() + slots[:, 3].sum())
                stats = mesi(cache, addr, is_write, target, n_t)
                t, bw, lat, lines, mig_bw = time_row(
                    stats, mig_rd, mig_wr, targets, cpu, mlp, ft)
                l2a = max(stats[2] + stats[3], 1)
                row = {"workload": label, "footprint_x_l2": k,
                       "policy": policy_label(pol), "cpu": cpu["kind"],
                       "time_ns": float(t),
                       "bw_dram_gbps": float(bw[0]),
                       "l2_miss_rate": stats[3] / l2a,
                       "lat_dram_ns": float(lat[0])}
                if n_t == 2:
                    row["bw_cxl_gbps"] = float(bw[1])
                    row["lat_cxl_ns"] = float(lat[1])
                else:
                    row["bw_cxl_gbps"] = float(sum(bw[1:], ft(0)))
                    agg = sum(lines[1:], ft(0))
                    row["lat_cxl_ns"] = float(
                        sum((lines[j] * lat[j] for j in range(1, n_t)),
                            ft(0)) / agg if agg > 0
                        else sum(lat[1:], ft(0)) / ft(n_t - 1))
                    for j in range(1, n_t):
                        row[f"bw_{tlabels[j]}_gbps"] = float(bw[j])
                        row[f"lat_{tlabels[j]}_ns"] = float(lat[j])
                row["bw_total_gbps"] = float(ft(row["bw_dram_gbps"])
                                             + ft(row["bw_cxl_gbps"]))
                if frac is not None:
                    row["migrated_pages"] = migrated
                    row["migration_gbps"] = float(mig_bw)
                    row["epoch_dram_frac"] = frac
                row["stats"] = dict(zip(names, stats))
                row["topology"] = topo_name
                if tierings:
                    row["tiering"] = tiering_label(tr)
                rows.append(row)
    return rows

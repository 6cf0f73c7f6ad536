"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract), then a
human-readable block per benchmark.

  fig5_llc_missrate   — paper Fig. 5: STREAM @ {2,4,6,8}xL2, two CPU models
  interleave_sweep    — paper §IV: DRAM:CXL page-interleave ratio sweep
  latency_bandwidth   — paper §III-B.2/§V: idle latency breakdown + loaded
                        latency ("banana") curves per tier
  programming_models  — paper §IV: zNUMA vs flat vs weighted interleave
  kv_tiering          — paper §I use-case: KV-cache spill plan + paged pool
  kernels_micro       — Pallas kernel micro-bench (interpret mode on CPU)
  topology            — multi-expander target routing: direct / interleaved
                        / switched topologies in one device program
  workloads           — beyond-STREAM generators (pointer_chase, gups,
                        kv_decode, moe_stream) x topologies, one program,
                        + the LLC cache-pollution probe
  tiering             — epoch-based dynamic tiering (TPP-style hot-page
                        promotion/demotion) vs static zNUMA, migration
                        traffic charged into the timing fixed point
  distribute          — sharded + streaming sweep executor: shard-count
                        scaling (rows/s) + a streaming run whose trace
                        exceeds the resident working-set cap, both
                        bitwise-equal to the single-program path
  sampling            — SMARTS sampled simulation vs exact on a >=10M
                        access streamed trace: detailed-access fraction,
                        wall-times, and the in-bench assert that every
                        exact counter lies inside the reported 95% CI
  resilience          — checkpointed, fault-tolerant sweeps: checkpoint
                        overhead %, crash->resume fast-forward time,
                        transient retry counts — every recovered run
                        bitwise-equal to the uninterrupted one
  fidelity            — load-dependent latency distributions + MSHR
                        backpressure + the CXL-SSD third tier: banana
                        curve per expander type, a distribution-enabled
                        sweep with p50<=p95<=p99 asserted per row, and
                        the zero-load == deterministic-legacy collapse
  roofline_summary    — reads experiments/roofline JSON (dry-run derived)

``--only`` takes a comma-separated list of suites (e.g. ``--only
engine,distribute``); suite names and the JSON output schemas are
documented in docs/engine.md.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke
from repro.core import CXLRAMSim, SimConfig
from repro.core import cache as cache_mod
from repro.core import engine as engine_mod
from repro.core import numa
from repro.core import route as route_mod
from repro.core import machine as machine_mod
from repro.core.machine import CPUModel
from repro.core.timing import TimingConfig, latency_bandwidth_curve
from repro.kernels import ops
from repro.memory import plan_serving, plan_training
from repro.memory.kvcache import PagedKVCache

ROWS: List[str] = []


def emit(name: str, us: float, derived: str) -> None:
    ROWS.append(f"{name},{us:.1f},{derived}")


def _sim(l2_kib: int = 128) -> CXLRAMSim:
    s = CXLRAMSim(SimConfig(
        dram_gib=16, expander_gib=(16,),
        cache=cache_mod.CacheParams(l1_bytes=16 * 1024, l1_ways=4,
                                    l2_bytes=l2_kib * 1024, l2_ways=8)))
    s.online("znuma")
    return s


# ---------------------------------------------------------------------------
def fig5_llc_missrate() -> None:
    """Fig. 5: LLC miss rate, STREAM at k x L2, Timing(inorder) vs O3."""
    sim = _sim()
    print("\n== fig5_llc_missrate (paper Fig. 5) ==")
    print(f"{'kxL2':>5} {'cpu':>8} {'llc_miss':>9} {'time_ms':>9} "
          f"{'bw_GB/s':>8}")
    for cpu in (CPUModel(kind="inorder", mlp=1), CPUModel(kind="o3", mlp=8)):
        t0 = time.time()
        rows = sim.stream_suite(footprint_factors=(2, 4, 6, 8),
                                policy=numa.ZNuma(1.0), cpu=cpu)
        dt = (time.time() - t0) * 1e6 / len(rows)
        for r in rows:
            print(f"{r['footprint_x_l2']:>5} {r['cpu']:>8} "
                  f"{r['l2_miss_rate']:>9.3f} {r['time_ns']/1e6:>9.2f} "
                  f"{r['bw_total_gbps']:>8.2f}")
        emit(f"fig5_{cpu.kind}", dt,
             f"llc_miss@8x={rows[-1]['l2_miss_rate']:.3f}")


def interleave_sweep() -> None:
    """§IV: OS page-interleave ratio between system DRAM and CXL."""
    sim = _sim()
    fp = 4 * sim.config.cache.l2_bytes
    print("\n== interleave_sweep (paper §IV) ==")
    print(f"{'policy':>18} {'time_ms':>9} {'bw_GB/s':>8} {'bw_dram':>8} "
          f"{'bw_cxl':>8} {'lat_cxl_ns':>10}")
    policies = [("dram-only", numa.ZNuma(0.0)),
                ("4:1", numa.WeightedInterleave(4, 1)),
                ("2:1", numa.WeightedInterleave(2, 1)),
                ("1:1", numa.WeightedInterleave(1, 1)),
                ("1:2", numa.WeightedInterleave(1, 2)),
                ("cxl-only", numa.ZNuma(1.0))]
    base = None
    for name, pol in policies:
        t0 = time.time()
        r = sim.run_stream("triad", fp, pol)
        us = (time.time() - t0) * 1e6
        base = base or r.time_ns
        print(f"{name:>18} {r.time_ns/1e6:>9.2f} "
              f"{r.achieved_gbps['total']:>8.2f} "
              f"{r.achieved_gbps['dram']:>8.2f} "
              f"{r.achieved_gbps['cxl']:>8.2f} "
              f"{r.loaded_latency_ns['cxl']:>10.1f}")
        emit(f"interleave_{name}", us,
             f"slowdown={r.time_ns/base:.2f}x")


def latency_bandwidth() -> None:
    """§III-B.2/§V: stage breakdown + loaded-latency curves."""
    t = TimingConfig()
    print("\n== latency_bandwidth (paper §III-B.2, §V) ==")
    print("CXL stage breakdown:", {k: round(v, 1) for k, v
                                   in t.cxl.stage_breakdown().items()})
    for kind in ("dram", "cxl"):
        t0 = time.time()
        curve = latency_bandwidth_curve(t, kind, n=8)
        us = (time.time() - t0) * 1e6
        knee = curve[np.argmax(curve[:, 2] > 2 * curve[0, 2]), 0] \
            if (curve[:, 2] > 2 * curve[0, 2]).any() else curve[-1, 0]
        print(f"{kind}: idle={curve[0,2]:.0f}ns "
              f"peak={t.peak_gbps(kind):.1f}GB/s knee~{knee:.1f}GB/s")
        emit(f"latency_curve_{kind}", us,
             f"idle_ns={curve[0,2]:.0f};peak={t.peak_gbps(kind):.1f}")


def programming_models() -> None:
    """§IV: zNUMA / flat / weighted-interleave programming models."""
    print("\n== programming_models (paper §IV) ==")
    sim = _sim()
    fp = 4 * sim.config.cache.l2_bytes
    dram_pages = (fp // 2) // numa.PAGE_BYTES
    cases = [("znuma-bind-cxl", numa.ZNuma(1.0)),
             ("flat-first-touch", numa.FlatMode(dram_pages=dram_pages)),
             ("weighted-1:1", numa.WeightedInterleave(1, 1))]
    for name, pol in cases:
        t0 = time.time()
        r = sim.run_stream("triad", fp, pol)
        us = (time.time() - t0) * 1e6
        print(f"{name:>18}: bw={r.achieved_gbps['total']:.2f}GB/s "
              f"dram/cxl split={r.achieved_gbps['dram']:.2f}/"
              f"{r.achieved_gbps['cxl']:.2f}")
        emit(f"progmodel_{name}", us,
             f"bw={r.achieved_gbps['total']:.2f}")


def kv_tiering() -> None:
    """Paper §I use-case: KV cache spill to CXL (plan + paged pool sim)."""
    print("\n== kv_tiering (paper §I LLM use-case) ==")
    t0 = time.time()
    plan = plan_serving(get_config("stablelm-12b"), batch=512,
                        context=131072)
    us = (time.time() - t0) * 1e6
    print(f"stablelm-12b serve 512x131072: hbm={plan.hbm_bytes/2**30:.1f}GiB "
          f"cxl={plan.cxl_bytes/2**30:.1f}GiB  {plan.note}")
    emit("kv_plan_stablelm", us, f"cxl_GiB={plan.cxl_bytes/2**30:.1f}")

    cfg = get_smoke("granite-3-8b")
    kv = PagedKVCache(cfg, n_pages=64, page_size=8, max_blocks=16,
                      hbm_page_budget=16)
    t0 = time.time()
    rng = np.random.default_rng(0)
    for sid in range(8):
        kv.allocate(sid)
        k = rng.standard_normal((40, cfg.n_kv_heads, cfg.head_dim)) \
            .astype(np.float32)
        kv.append_tokens(sid, 0, k, k)
    for _ in range(4):
        kv.gather_args(list(range(8)))
    us = (time.time() - t0) * 1e6
    s = kv.stats
    print(f"paged pool: {kv.tier_histogram()} fetches={s.cxl_fetches} "
          f"promos={s.promotions} sim_cxl={s.sim_seconds*1e3:.2f}ms")
    emit("kv_paged_pool", us, f"cxl_fetches={s.cxl_fetches}")

    t0 = time.time()
    tplan = plan_training(get_config("deepseek-v3-671b"))
    us = (time.time() - t0) * 1e6
    off = {p.name: p.tier for p in tplan.placements if p.tier != "hbm"}
    print(f"deepseek-v3 train@256: spills={off} "
          f"cxl_term={tplan.cxl_seconds:.2f}s/step")
    emit("offload_plan_deepseek", us, f"cxl_s={tplan.cxl_seconds:.2f}")


def kernels_micro() -> None:
    """Pallas kernels in interpret mode (correct-path timing on CPU)."""
    print("\n== kernels_micro (interpret mode) ==")
    rng = np.random.default_rng(0)

    def timeit(fn, *a, reps=3, **kw):
        fn(*a, **kw)                      # compile/warm
        t0 = time.time()
        for _ in range(reps):
            jax.block_until_ready(fn(*a, **kw))
        return (time.time() - t0) / reps * 1e6

    b = jnp.asarray(rng.standard_normal((64, 512)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((64, 512)), jnp.float32)
    us = timeit(ops.stream_triad, b, c, 3.0)
    emit("kernel_triad", us, f"GBps={3*b.nbytes/us*1e-3:.2f}")
    print(f"triad {us:.0f}us")

    q = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
    us = timeit(ops.flash_attention, q, k, k)
    emit("kernel_flash", us, "shape=1x4x256x64")
    print(f"flash {us:.0f}us")

    qd = jnp.asarray(rng.standard_normal((4, 8, 64)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((32, 16, 2, 64)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, 32, (4, 4)), jnp.int32)
    cl = jnp.full((4,), 64, jnp.int32)
    us = timeit(ops.paged_attention, qd, kp, kp, bt, cl)
    emit("kernel_paged", us, "pool=32x16")
    print(f"paged {us:.0f}us")

    addr = jnp.asarray(rng.integers(0, 4096, 4096), jnp.int32)
    us = timeit(ops.cache_sim, addr, n_sets=64, n_ways=4, chunk=512)
    emit("kernel_cache_sim", us, f"Maccess/s={4096/us:.2f}")
    print(f"cache_sim {us:.0f}us")


def engine() -> None:
    """Batched trace engine vs the seed's sequential per-config loop.

    Runs the default §IV suite (4 footprints x 2 policies x 2 CPU models):
    once as the sequential Python loop (one scan dispatch per
    configuration, as the seed did) and once through
    `repro.core.engine.run_sweep` (one vmapped device program).  Reports
    trace throughput and sweep wall-clock, verifies the stats are
    bitwise-equal, and writes `BENCH_engine.json` at the repo root.
    """
    print("\n== engine (batched trace engine vs sequential loop) ==")
    sim = _sim(l2_kib=64)
    fps = (2, 4, 6, 8)
    policies = (numa.ZNuma(1.0), numa.WeightedInterleave(1, 1))
    cpus = (CPUModel(kind="inorder", mlp=1), CPUModel(kind="o3", mlp=8))

    # --- sequential baseline: the seed loop — one `lax.scan` dispatch
    # (and per-trace-length compile) per configuration, plain ungated step,
    # scalar Picard per config.  Run twice: cold (with its 4 compiles) and
    # warm, so both speedup numbers are like-for-like. ---
    from repro.core import stream as stream_mod
    from repro.core.machine import Machine

    def sequential() -> List[Dict]:
        rows: List[Dict] = []
        for cpu in cpus:
            machine = Machine(sim.config.cache, sim.config.timing, cpu)
            for pol in policies:
                for k in fps:
                    layout = stream_mod.layout_for_footprint(
                        k * sim.config.cache.l2_bytes)
                    addr, is_write = stream_mod.stream_trace("triad", layout)
                    tier = numa.tier_of_lines(pol, addr, layout.n_pages)
                    stats, _ = machine.simulate(addr, is_write, tier)
                    r = machine._time(stats)
                    rows.append({"footprint_x_l2": k,
                                 "policy": numa.describe(pol),
                                 "cpu": r.cpu, "stats": r.stats})
        return rows

    t0 = time.time()
    seq_rows = sequential()
    t_seq_cold = time.time() - t0
    t0 = time.time()
    seq_rows = sequential()
    t_seq = time.time() - t0          # warm: scan executions only

    # --- batched engine: the whole grid as one device program ---
    spec = engine_mod.SweepSpec(footprint_factors=fps, policies=policies,
                                cpus=cpus)
    run = lambda: engine_mod.run_sweep(spec, sim.config.cache,
                                       sim.config.timing)
    t0 = time.time()
    bat_rows = run()
    t_cold = time.time() - t0          # includes the single compilation
    t0 = time.time()
    bat_rows = run()
    t_warm = time.time() - t0

    # --- pallas backend: same sweep through the MESI kernel (compiled
    # on TPU hosts; interpret mode on CPU is the parity oracle, so its
    # throughput is reported but not a speed claim) ---
    pal_spec = dataclasses.replace(spec, backend="pallas")
    run_pal = lambda: engine_mod.run_sweep(pal_spec, sim.config.cache,
                                           sim.config.timing)
    t0 = time.time()
    pal_rows = run_pal()
    t_pal_cold = time.time() - t0
    t0 = time.time()
    pal_rows = run_pal()
    t_pal_warm = time.time() - t0
    pallas_mode = ("compiled" if jax.default_backend() == "tpu"
                   else "interpret")

    # --- bitwise stats check (sequential vs batched row-by-row) ---
    key = lambda r: (r["footprint_x_l2"], r["policy"], r["cpu"])
    seq_by, bat_by, pal_by = ({key(r): r["stats"] for r in rows}
                              for rows in (seq_rows, bat_rows, pal_rows))
    assert seq_by.keys() == bat_by.keys()
    stats_equal = all(seq_by[k] == bat_by[k] for k in seq_by)
    assert stats_equal, "batched stats diverged from the sequential path"
    pallas_equal = bat_by == pal_by
    assert pallas_equal, "pallas stats diverged from the reference path"

    # accesses actually simulated: one per (footprint, policy) cell — CPU
    # models share the cell's stats (sequential re-simulates per CPU)
    cells = {(r["footprint_x_l2"], r["policy"]):
             r["stats"]["l1_hit"] + r["stats"]["l1_miss"]
             for r in bat_rows}
    n_acc = sum(cells.values())
    n_acc_seq = n_acc * len(cpus)
    seq_rate = n_acc_seq / t_seq / 1e6
    cold_rate = n_acc / t_cold / 1e6
    warm_rate = n_acc / t_warm / 1e6
    pal_rate = n_acc / t_pal_warm / 1e6
    report = {
        "suite": {"footprint_factors": list(fps),
                  "policies": [numa.describe(p) for p in policies],
                  "cpus": [c.kind for c in cpus],
                  "l2_kib": sim.config.cache.l2_bytes // 1024,
                  "rows": len(bat_rows), "accesses": n_acc,
                  "accesses_sequential": n_acc_seq},
        "sequential_cold_s": round(t_seq_cold, 4),
        "sequential_warm_s": round(t_seq, 4),
        "batched_cold_s": round(t_cold, 4),
        "batched_warm_s": round(t_warm, 4),
        # headline: steady-state sweep vs steady-state loop (both warm)
        "speedup": round(t_seq / t_warm, 2),
        "speedup_cold": round(t_seq_cold / t_cold, 2),
        "speedup_warm": round(t_seq / t_warm, 2),
        "seq_maccess_per_s": round(seq_rate, 3),
        "batched_cold_maccess_per_s": round(cold_rate, 3),
        "batched_warm_maccess_per_s": round(warm_rate, 3),
        "stats_bitwise_equal": stats_equal,
        "pallas_cold_s": round(t_pal_cold, 4),
        "pallas_warm_s": round(t_pal_warm, 4),
        "pallas_warm_maccess_per_s": round(pal_rate, 3),
        "pallas_vs_reference_speedup": round(t_warm / t_pal_warm, 2),
        "pallas_stats_bitwise_equal": pallas_equal,
        "pallas_mode": pallas_mode,
    }
    out = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_engine.json"
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"sequential cold {t_seq_cold:.2f}s warm {t_seq:.2f}s "
          f"({seq_rate:.2f} Macc/s) | batched cold {t_cold:.2f}s "
          f"({cold_rate:.2f} Macc/s) warm {t_warm:.2f}s "
          f"({warm_rate:.2f} Macc/s)")
    print(f"speedup: {report['speedup_cold']}x cold/cold / "
          f"{report['speedup_warm']}x warm/warm; bitwise stats equal: "
          f"{stats_equal}  -> {out.name}")
    print(f"pallas ({pallas_mode}): warm {t_pal_warm:.2f}s "
          f"({pal_rate:.2f} Macc/s), "
          f"{report['pallas_vs_reference_speedup']}x vs reference; "
          f"bitwise stats equal: {pallas_equal}")
    emit("engine_sequential", t_seq * 1e6 / len(seq_rows),
         f"Maccess/s={seq_rate:.2f}")
    emit("engine_batched", t_warm * 1e6 / len(bat_rows),
         f"Maccess/s={warm_rate:.2f};speedup={report['speedup_warm']:.2f}x")
    emit("engine_pallas", t_pal_warm * 1e6 / len(pal_rows),
         f"Maccess/s={pal_rate:.2f};"
         f"vs_ref={report['pallas_vs_reference_speedup']:.2f}x;"
         f"mode={pallas_mode}")


def topology() -> None:
    """Multi-expander target routing: >=3 topologies, one device program.

    Sweeps {1x direct, 2x interleaved direct, 4x behind one switch} x
    footprints x policies through the batched engine — a single vmapped
    cache-sim dispatch covers every cell (stats padded to the widest
    target count) — and reports per-target achieved GB/s + loaded latency.
    Verifies the direct1 rows are bitwise-equal to the binary-tier path
    and writes `BENCH_topology.json` at the repo root.
    """
    print("\n== topology (multi-expander target routing) ==")
    cache = cache_mod.CacheParams(l1_bytes=16 * 1024, l1_ways=4,
                                  l2_bytes=64 * 1024, l2_ways=8)
    timing = TimingConfig()
    fps = (2, 4, 8)
    policies = (numa.ZNuma(1.0), numa.WeightedInterleave(1, 1))
    cpus = (CPUModel(kind="o3", mlp=8),)
    topos = (route_mod.direct(1), route_mod.direct(2), route_mod.switched(4))

    spec = engine_mod.SweepSpec(footprint_factors=fps, policies=policies,
                                cpus=cpus, topologies=topos)
    run = lambda: engine_mod.run_sweep(spec, cache, timing)
    t0 = time.time()
    rows = run()
    t_cold = time.time() - t0
    t0 = time.time()
    rows = run()
    t_warm = time.time() - t0

    # parity: direct1 rows vs the binary-tier path (no topology axis)
    binary = engine_mod.run_sweep(
        engine_mod.SweepSpec(footprint_factors=fps, policies=policies,
                             cpus=cpus), cache, timing)
    d1 = [r for r in rows if r["topology"] == "direct1"]
    parity = all(a["stats"] == b["stats"] for a, b in zip(d1, binary))
    assert parity, "direct1 topology diverged from the binary-tier path"

    print(f"{'topology':>10} {'kxL2':>5} {'policy':>18} {'bw_cxl':>7} "
          f"{'lat_cxl':>8}  per-target GB/s")
    for r in rows:
        per = [f"{r[k]:.2f}" for k in machine_mod.per_target_bw_columns(r)]
        print(f"{r['topology']:>10} {r['footprint_x_l2']:>5} "
              f"{r['policy']:>18} {r['bw_cxl_gbps']:>7.2f} "
              f"{r['lat_cxl_ns']:>8.1f}  [{', '.join(per)}]")

    n_acc = sum(r["stats"]["l1_hit"] + r["stats"]["l1_miss"] for r in rows)
    report = {
        "suite": {"topologies": [t.name for t in topos],
                  "footprint_factors": list(fps),
                  "policies": [numa.describe(p) for p in policies],
                  "cpus": [c.kind for c in cpus],
                  "rows": len(rows), "accesses": n_acc,
                  "one_device_program": True},
        "cold_s": round(t_cold, 4),
        "warm_s": round(t_warm, 4),
        "direct1_bitwise_equals_binary_tier": parity,
        "rows": [{k: v for k, v in r.items() if k != "stats"}
                 for r in rows],
    }
    out = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_topology.json"
    out.write_text(json.dumps(report, indent=1) + "\n")
    print(f"{len(topos)} topologies x {len(fps)} footprints x "
          f"{len(policies)} policies in one program: cold {t_cold:.2f}s "
          f"warm {t_warm:.2f}s; direct1 bitwise==binary: {parity} "
          f"-> {out.name}")
    emit("topology_sweep", t_warm * 1e6 / len(rows),
         f"topos={len(topos)};parity={parity}")


def workloads() -> None:
    """Workload generators beyond STREAM across topologies, one program.

    Sweeps all four on-device generators (pointer_chase, gups, kv_decode,
    moe_stream) x {direct1, switch4} topologies x footprints through the
    batched engine — a single vmapped cache-sim dispatch covers every
    cell.  Asserts the device-generated kv_decode stats are bitwise-equal
    to the NumPy host-reference trace, measures the LLC pollution metric
    (L2 miss-rate delta of a DRAM-resident probe with/without a
    concurrent CXL burst), and writes `BENCH_workloads.json`.
    """
    import dataclasses

    from repro.workloads import (Gups, KVDecode, MoEStream, PointerChase,
                                 pollution_probe)

    print("\n== workloads (beyond-STREAM generators, one device program) ==")
    cache = cache_mod.CacheParams(l1_bytes=16 * 1024, l1_ways=4,
                                  l2_bytes=64 * 1024, l2_ways=8)
    timing = TimingConfig()
    wls = (PointerChase(), Gups(), KVDecode(), MoEStream())
    topos = (route_mod.direct(1), route_mod.switched(4))
    fps = (2, 4)
    spec = engine_mod.SweepSpec(
        footprint_factors=fps, policies=(numa.ZNuma(1.0),),
        cpus=(CPUModel(kind="o3", mlp=8),), workloads=wls,
        topologies=topos)
    run = lambda: engine_mod.run_sweep(spec, cache, timing)
    t0 = time.time()
    rows = run()
    t_cold = time.time() - t0
    t0 = time.time()
    rows = run()
    t_warm = time.time() - t0

    # device-vs-host parity: the kv_decode trace re-derived with the NumPy
    # reference generator, routed through the same committed decoders,
    # must produce bitwise-equal stats
    kv, k = wls[2], fps[0]
    route = route_mod.build_route(topos[0], timing)
    ht = kv.host_trace(k * cache.l2_bytes)
    tier = route.targets_of_tiered_lines(ht.tier, ht.addr)
    p = dataclasses.replace(cache, n_targets=route.n_targets)
    stats, _ = engine_mod.run_traces(
        p, jnp.asarray(ht.addr)[None], jnp.asarray(ht.is_write)[None],
        core=None, tier=jnp.asarray(tier)[None])
    want = cache_mod.stats_dict(np.asarray(stats[0]))
    got = next(r["stats"] for r in rows
               if r["workload"] == kv.name and r["footprint_x_l2"] == k
               and r["topology"] == topos[0].name)
    kv_parity = got == want
    assert kv_parity, "device kv_decode stats diverged from host reference"

    pollution = pollution_probe(cache)

    print(f"{'workload':>14} {'topology':>9} {'kxL2':>5} {'bw_GB/s':>8} "
          f"{'bw_cxl':>7} {'lat_cxl':>8} {'llc_miss':>9}")
    for r in rows:
        print(f"{r['workload']:>14} {r['topology']:>9} "
              f"{r['footprint_x_l2']:>5} {r['bw_total_gbps']:>8.2f} "
              f"{r['bw_cxl_gbps']:>7.2f} {r['lat_cxl_ns']:>8.1f} "
              f"{r['l2_miss_rate']:>9.3f}")
    print(f"LLC pollution probe: clean "
          f"{pollution['probe_miss_rate_clean']:.3f} -> polluted "
          f"{pollution['probe_miss_rate_polluted']:.3f} "
          f"(delta {pollution['pollution_delta']:.3f})")

    n_acc = sum(r["stats"]["l1_hit"] + r["stats"]["l1_miss"] for r in rows)
    report = {
        "suite": {"workloads": [w.name for w in wls],
                  "topologies": [t.name for t in topos],
                  "footprint_factors": list(fps),
                  "policies": [numa.describe(p_) for p_ in spec.policies],
                  "cpus": [c.kind for c in spec.cpus],
                  "rows": len(rows), "accesses": n_acc,
                  "one_device_program": True},
        "cold_s": round(t_cold, 4),
        "warm_s": round(t_warm, 4),
        "kv_decode_device_bitwise_equals_host_reference": kv_parity,
        "pollution": pollution,
        "rows": [{k_: v for k_, v in r.items() if k_ != "stats"}
                 for r in rows],
    }
    out = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_workloads.json"
    out.write_text(json.dumps(report, indent=1) + "\n")
    print(f"{len(wls)} workloads x {len(topos)} topologies x {len(fps)} "
          f"footprints in one program: cold {t_cold:.2f}s warm "
          f"{t_warm:.2f}s; kv device==host: {kv_parity} -> {out.name}")
    emit("workloads_sweep", t_warm * 1e6 / len(rows),
         f"wls={len(wls)};kv_parity={kv_parity};"
         f"pollution={pollution['pollution_delta']:.3f}")


def tiering() -> None:
    """Epoch-based dynamic tiering vs static zNUMA placement.

    Sweeps {static, two TPP-style tiering points} x {hot_cold, gups,
    kv_decode} through the batched engine — the whole grid, static rows
    included, is ONE vmapped epoch-structured device program
    (`repro.core.tiering_dyn`).  The hot/cold workload's stationary
    skew is what dynamic promotion exploits: after the first epoch the
    hot page set lives in DRAM and the *effective* bandwidth (demand
    bytes over runtime, migration excluded) beats the static zNUMA bind
    that left it on CXL — while the migration traffic itself is charged
    into the timing fixed point and reported per row.  Asserts the win
    and writes `BENCH_tiering.json`.
    """
    from repro.core import tiering_dyn as td
    from repro.core.spec import CACHELINE_BYTES
    from repro.workloads import Gups, HotCold, KVDecode

    print("\n== tiering (dynamic hot-page promotion vs static zNUMA) ==")
    cache = cache_mod.CacheParams(l1_bytes=16 * 1024, l1_ways=4,
                                  l2_bytes=32 * 1024, l2_ways=8)
    timing = TimingConfig()
    wls = (HotCold(hot_page_frac=0.25), Gups(), KVDecode())
    tiers = (None,
             td.DynamicTiering(epoch_len=2048, budget=16, threshold=8),
             td.DynamicTiering(epoch_len=4096, budget=8, threshold=8))
    spec = engine_mod.SweepSpec(
        footprint_factors=(8,), policies=(numa.ZNuma(1.0),),
        cpus=(CPUModel(kind="o3", mlp=8),), workloads=wls, tiering=tiers)
    run = lambda: engine_mod.run_sweep(spec, cache, timing)
    t0 = time.time()
    rows = run()
    t_cold = time.time() - t0
    t0 = time.time()
    rows = run()
    t_warm = time.time() - t0

    # --- pallas backend: the same epoch-structured grid through the
    # dynamic MESI kernel (compiled on TPU; interpret-mode parity
    # oracle on CPU hosts) ---
    pal_spec = dataclasses.replace(spec, backend="pallas")
    run_pal = lambda: engine_mod.run_sweep(pal_spec, cache, timing)
    t0 = time.time()
    pal_rows = run_pal()
    t_pal_cold = time.time() - t0
    t0 = time.time()
    pal_rows = run_pal()
    t_pal_warm = time.time() - t0
    pallas_equal = pal_rows == rows    # dict equality: floats to the bit
    assert pallas_equal, "pallas tiering rows diverged from reference"
    pallas_mode = ("compiled" if jax.default_backend() == "tpu"
                   else "interpret")

    def eff_bw(r):
        """Demand bytes (migration excluded) over the converged runtime."""
        s = r["stats"]
        demand = sum(v for k, v in s.items()
                     if k.startswith(("mem_read", "mem_write")))
        return demand * CACHELINE_BYTES / max(r["time_ns"], 1.0)

    print(f"{'workload':>10} {'tiering':>22} {'time_ms':>8} {'eff_GB/s':>9} "
          f"{'mig_GB/s':>9} {'migrated':>9} {'dram_frac e0->eN':>17}")
    for r in rows:
        fr = r.get("epoch_dram_frac")
        fr_s = f"{fr[0]:.2f}->{fr[-1]:.2f}" if fr else "-"
        print(f"{r['workload']:>10} {r['tiering']:>22} "
              f"{r['time_ns']/1e6:>8.2f} {eff_bw(r):>9.2f} "
              f"{r.get('migration_gbps', 0.0):>9.2f} "
              f"{r.get('migrated_pages', '-'):>9} {fr_s:>17}")

    by = {(r["workload"], r["tiering"]): r for r in rows}
    static = by[("hot_cold", "static")]
    dyn = by[("hot_cold", tiers[1].label)]
    win = eff_bw(dyn) / eff_bw(static)
    assert dyn["time_ns"] < static["time_ns"], \
        "dynamic tiering must beat static zNUMA on the hot/cold workload"
    assert eff_bw(dyn) > eff_bw(static)
    assert dyn["migration_gbps"] > 0.0 and dyn["migrated_pages"] > 0, \
        "migration traffic must be visible in the timed row"

    report = {
        "suite": {"workloads": [w.name for w in wls],
                  "tiering": [td.describe(t) for t in tiers],
                  "footprint_factors": [8],
                  "policy": numa.describe(spec.policies[0]),
                  "rows": len(rows), "one_device_program": True},
        "cold_s": round(t_cold, 4),
        "warm_s": round(t_warm, 4),
        "pallas_cold_s": round(t_pal_cold, 4),
        "pallas_warm_s": round(t_pal_warm, 4),
        "pallas_vs_reference_speedup": round(t_warm / t_pal_warm, 3),
        "pallas_rows_bitwise_equal": pallas_equal,
        "pallas_mode": pallas_mode,
        "hot_cold_effective_bw_win": round(win, 3),
        "hot_cold_speedup": round(static["time_ns"] / dyn["time_ns"], 3),
        "hot_cold_migration_gbps": round(dyn["migration_gbps"], 3),
        "static_rows_bitwise_equal_legacy": True,  # tier-1 enforced
        "rows": [{k: v for k, v in r.items() if k != "stats"}
                 | {"effective_gbps": round(eff_bw(r), 3)}
                 for r in rows],
    }
    out = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_tiering.json"
    out.write_text(json.dumps(report, indent=1) + "\n")
    print(f"hot_cold: dynamic beats static zNUMA {win:.2f}x on effective "
          f"bandwidth ({static['time_ns']/dyn['time_ns']:.2f}x faster) "
          f"while moving {dyn['migrated_pages']} pages at "
          f"{dyn['migration_gbps']:.2f} GB/s -> {out.name}")
    print(f"pallas ({pallas_mode}): warm {t_pal_warm:.2f}s, "
          f"{report['pallas_vs_reference_speedup']}x vs reference; "
          f"rows bitwise equal: {pallas_equal}")
    emit("tiering_sweep", t_warm * 1e6 / len(rows),
         f"eff_bw_win={win:.2f}x;mig_gbps={dyn['migration_gbps']:.2f}")
    emit("tiering_pallas", t_pal_warm * 1e6 / len(pal_rows),
         f"vs_ref={report['pallas_vs_reference_speedup']:.2f}x;"
         f"mode={pallas_mode}")


def distribute() -> None:
    """Sharded + streaming sweep executor (`repro.core.distribute`).

    (1) Shard-count scaling: the default §IV grid (4 footprints x 2
    policies x 2 CPU models) re-run at 1/2/4 row-shards through the
    pmap-based executor, reporting sweep throughput (rows/s) per shard
    count and asserting every variant is bitwise-equal to the
    single-program engine path.  On a 1-device host the super-steps
    serialize, so the curve is the documented flat-line (shards still
    bound per-program batch memory); with D devices shards overlap.
    (2) Streaming: a trace whose resident working set exceeds a device
    budget, generated segment-by-segment and threaded through the scan
    carry — bounded memory, stats bitwise-equal to the resident run.
    Writes `BENCH_distribute.json`.
    """
    from repro.core import distribute as dist_mod

    print("\n== distribute (sharded + streaming sweep executor) ==")
    cache = cache_mod.CacheParams(l1_bytes=16 * 1024, l1_ways=4,
                                  l2_bytes=64 * 1024, l2_ways=8)
    timing = TimingConfig()
    spec = engine_mod.SweepSpec(
        footprint_factors=(2, 4, 6, 8),
        policies=(numa.ZNuma(1.0), numa.WeightedInterleave(1, 1)),
        cpus=(CPUModel(kind="inorder", mlp=1), CPUModel(kind="o3", mlp=8)))
    base_rows = engine_mod.run_sweep(spec, cache, timing)
    n_dev = len(jax.local_devices())

    scaling = []
    parity = True
    best = (0.0, 1)
    for shards in (1, 2, 4):
        run = lambda: dist_mod.run_sweep(spec, cache, timing, mesh=shards)
        rows = run()                               # compile
        t0 = time.time()
        rows = run()
        warm = time.time() - t0
        parity = parity and rows == base_rows
        rate = len(rows) / warm
        if rate > best[0]:
            best = (rate, shards)
        scaling.append({"shards": shards, "warm_s": round(warm, 4),
                        "rows_per_s": round(rate, 2)})
        print(f"  shards={shards}: warm {warm:.3f}s "
              f"({rate:.1f} rows/s, {n_dev} device(s))")
    assert parity, "sharded rows diverged from the single-program sweep"

    # --- streaming: trace bytes beyond a resident working-set cap ---------
    b_rows, seg, reps = 4, 32768, 12
    n_total = seg * reps
    cap_bytes = 8 << 20                   # the "device" trace budget
    resident = dist_mod.trace_working_set_bytes(b_rows, n_total)
    seg_bytes = dist_mod.trace_working_set_bytes(b_rows, seg)
    assert resident > cap_bytes > seg_bytes
    rng = np.random.default_rng(5)
    base = (rng.integers(0, 4096, (b_rows, seg)).astype(np.int32),
            rng.integers(0, 2, (b_rows, seg)).astype(np.int32),
            rng.integers(0, 2, (b_rows, seg)).astype(np.int32))

    def source():
        for _ in range(reps):                  # generated, never stacked
            yield (base[0], base[1], None, base[2])

    p = cache
    s_stream, _ = dist_mod.stream_traces(p, source())    # compile
    t0 = time.time()
    s_stream, _ = dist_mod.stream_traces(p, source())
    jax.block_until_ready(s_stream)
    t_stream = time.time() - t0
    full = tuple(np.tile(a, (1, reps)) for a in base)
    s_res, _ = engine_mod.run_traces(p, full[0], full[1], None, full[2])
    t0 = time.time()
    s_res, _ = engine_mod.run_traces(p, full[0], full[1], None, full[2])
    jax.block_until_ready(s_res)
    t_res = time.time() - t0
    stream_parity = bool((np.asarray(s_stream) == np.asarray(s_res)).all())
    assert stream_parity, "streamed stats diverged from the resident scan"
    acc = b_rows * n_total
    print(f"  streaming: {b_rows} rows x {n_total} accesses "
          f"({resident / 2**20:.1f} MiB resident > {cap_bytes / 2**20:.0f} "
          f"MiB cap; {seg_bytes / 2**20:.1f} MiB/segment) "
          f"streamed {t_stream:.2f}s vs resident {t_res:.2f}s; "
          f"bitwise equal: {stream_parity}")
    print(f"sweep-throughput: {best[0]:.1f} rows/s "
          f"(shards={best[1]}, {n_dev} device(s))")

    report = {
        "suite": {"footprint_factors": [2, 4, 6, 8],
                  "policies": [numa.describe(p_) for p_ in spec.policies],
                  "cpus": [c.kind for c in spec.cpus],
                  "rows": len(base_rows)},
        "n_devices": n_dev,
        "shard_scaling": scaling,
        "sharded_bitwise_equal_single_program": parity,
        "sweep_rows_per_s": round(best[0], 2),
        "single_device_note": (
            "1-device host: super-steps serialize, so shard scaling is a "
            "flat-line (shards still bound per-program batch memory); "
            "with D devices shards overlap via pmap"
            if n_dev == 1 else None),
        "streaming": {
            "rows": b_rows, "trace_len": n_total, "segment": seg,
            "resident_bytes": resident, "cap_bytes": cap_bytes,
            "segment_bytes": seg_bytes,
            "exceeds_resident_cap": resident > cap_bytes,
            "streamed_warm_s": round(t_stream, 4),
            "resident_warm_s": round(t_res, 4),
            "maccess_per_s_streamed": round(acc / t_stream / 1e6, 3),
            "bitwise_equal_resident": stream_parity,
        },
    }
    out = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_distribute.json"
    out.write_text(json.dumps(report, indent=1) + "\n")
    print(f"-> {out.name}")
    emit("distribute_shards", 1e6 / best[0],
         f"rows_per_s={best[0]:.1f};shards={best[1]};parity={parity}")
    emit("distribute_stream", t_stream * 1e6,
         f"Maccess/s={acc / t_stream / 1e6:.2f};parity={stream_parity}")


def sampling() -> None:
    """SMARTS sampled simulation vs the exact run (`repro.core.sampling`).

    A >=10M-access GUPS trace streamed through the scan carry, run exact
    and SMARTS-sampled (w=1, m=1, p=8 -> 12.5% of accesses measured in
    detail) — wall-time for both, detailed-access counts, and the
    statistical contract asserted in-bench: every counter's exact value
    must lie inside the sampled row's reported 95% interval.  Functional
    warming keeps the cache/tier state machine at full fidelity through
    the masked slots (that is what makes the windows unbiased), so
    wall-time is NOT the win — detailed stat collection is; both numbers
    land in the report.  Writes `BENCH_sampling.json`.
    """
    from repro.core import distribute as dist_mod
    from repro.core.sampling import SamplingSpec
    from repro.workloads import Gups

    print("\n== sampling (SMARTS sampled simulation vs exact) ==")
    cache = cache_mod.CacheParams(l1_bytes=8 * 1024, l1_ways=2,
                                  l2_bytes=16 * 1024, l2_ways=8)
    timing = TimingConfig()
    wl = Gups(updates_per_line=2560)      # 2 * 2560 * 2048 = 10.49M
    sp = SamplingSpec(warm_slots=1, measure_slots=1, period_slots=8)
    chunk = 1 << 20

    def sweep(samp):
        return dist_mod.run_sweep(
            engine_mod.SweepSpec(
                footprint_factors=(8,), policies=(numa.ZNuma(1.0),),
                cpus=(CPUModel(kind="o3", mlp=8),), workloads=(wl,),
                sampling=samp),
            cache, timing, stream_chunk=chunk)

    t0 = time.time()
    [r_ex] = sweep((None,))
    t_exact = time.time() - t0
    t0 = time.time()
    [r_sm] = sweep((sp,))
    t_samp = time.time() - t0

    total = r_ex["stats"]["l1_hit"] + r_ex["stats"]["l1_miss"]
    assert total >= 10_000_000, f"trace too short for the contract: {total}"
    detailed = int(round(r_sm["sampled_frac"] * total))
    assert r_sm["sampled_frac"] <= 0.20, (
        f"sampled mode must measure <=20% of accesses in detail, got "
        f"{r_sm['sampled_frac']:.3f}")

    # the statistical contract: exact value inside the reported interval
    # for EVERY counter, and for the derived LLC miss rate
    misses = []
    for k, v in r_ex["stats"].items():
        err = abs(r_sm["stats"][k] - v)
        if err > r_sm[f"{k}_ci95"]:
            misses.append((k, err, r_sm[f"{k}_ci95"]))
    assert not misses, f"estimates outside their 95% CI: {misses}"
    rate_err = abs(r_sm["l2_miss_rate"] - r_ex["l2_miss_rate"])
    assert rate_err <= r_sm["l2_miss_rate_ci95"]

    rel = {k: abs(r_sm["stats"][k] - v) / v
           for k, v in r_ex["stats"].items() if v}
    worst = max(rel, key=rel.get)
    print(f"  {total / 1e6:.1f}M accesses, {r_sm['sample_windows']} "
          f"measurement windows: exact {t_exact:.2f}s vs sampled "
          f"{t_samp:.2f}s; {detailed / 1e6:.2f}M accesses "
          f"({r_sm['sampled_frac']:.1%}) measured in detail")
    print(f"  worst relative error {worst}={rel[worst]:.4%}; "
          f"llc miss rate {r_sm['l2_miss_rate']:.5f} +/- "
          f"{r_sm['l2_miss_rate_ci95']:.5f} (exact "
          f"{r_ex['l2_miss_rate']:.5f}); all counters inside their CI")

    report = {
        "suite": {"workload": wl.name, "accesses": total,
                  "footprint_x_l2": 8, "sampling": r_sm["sampling"],
                  "stream_chunk": chunk, "one_device_program": True},
        "exact_warm_s": round(t_exact, 4),
        "sampled_warm_s": round(t_samp, 4),
        "detailed_accesses": detailed,
        "sampled_frac": r_sm["sampled_frac"],
        "sample_windows": r_sm["sample_windows"],
        "all_counters_within_ci95": not misses,
        "l2_miss_rate_within_ci95": bool(
            rate_err <= r_sm["l2_miss_rate_ci95"]),
        "worst_rel_error": {"counter": worst,
                            "rel_error": round(rel[worst], 6)},
        "wall_time_note": (
            "functional warming runs the cache model at full fidelity "
            "through masked slots (unbiased windows), so wall-time is "
            "comparable; the win is detailed stat collection"),
        "rows": [{k: v for k, v in r.items() if k != "stats"}
                 for r in (r_ex, r_sm)],
    }
    out = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_sampling.json"
    out.write_text(json.dumps(report, indent=1) + "\n")
    print(f"-> {out.name}")
    emit("sampling_exact", t_exact * 1e6, f"Maccess={total / 1e6:.1f}")
    emit("sampling_sampled", t_samp * 1e6,
         f"detail_frac={r_sm['sampled_frac']:.3f};"
         f"within_ci={not misses}")


def resilience() -> None:
    """Checkpointed, fault-tolerant sweep runtime (`repro.core.resilience`).

    (1) Checkpoint overhead: a streamed sweep (512-access segments) run
    plain vs carry-checkpointed every 2 segments (blocking writes to a
    tempdir) — overhead %, rows bitwise-equal.  (2) Resume: the same run
    killed by an injected crash late in the sweep, then resumed from its
    checkpoints — fast-forwarded segment count + resume wall time,
    resumed rows bitwise-equal to the uninterrupted run.  (3) Retry: a
    twice-firing transient device fault absorbed by exponential backoff —
    retry count, rows unchanged.  Writes `BENCH_resilience.json`.
    """
    import tempfile

    from repro.core import distribute as dist_mod
    from repro.core import resilience as res_mod

    print("\n== resilience (checkpointed, fault-tolerant sweeps) ==")
    cache = cache_mod.CacheParams(l1_bytes=8 * 1024, l1_ways=2,
                                  l2_bytes=16 * 1024, l2_ways=8)
    timing = TimingConfig()
    spec = engine_mod.SweepSpec(
        footprint_factors=(2,),
        policies=(numa.WeightedInterleave(1, 1), numa.ZNuma(1.0)),
        cpus=(CPUModel(kind="o3", mlp=8),))
    seg = 512

    run_plain = lambda: dist_mod.run_sweep(spec, cache, timing,
                                           stream_chunk=seg)
    base_rows = run_plain()                       # compile
    t0 = time.time()
    base_rows = run_plain()
    t_plain = time.time() - t0

    # --- checkpoint overhead (warm, fresh directory per run) --------------
    def run_ckpt(d):
        pol = res_mod.CheckpointPolicy(d, every_segments=2, blocking=True)
        rep = res_mod.RunReport()
        rows = dist_mod.run_sweep(spec, cache, timing, stream_chunk=seg,
                                  resume=pol, report=rep)
        return rows, rep

    with tempfile.TemporaryDirectory() as d:
        run_ckpt(d)                               # warm the resilient path
    with tempfile.TemporaryDirectory() as d:
        t0 = time.time()
        rows_c, rep_c = run_ckpt(d)
        t_ckpt = time.time() - t0
    ckpt_parity = rows_c == base_rows
    assert ckpt_parity, "checkpointed rows diverged from the plain sweep"
    overhead_pct = (t_ckpt - t_plain) / t_plain * 100.0
    n_ckpts = rep_c.count("checkpoint")
    ckpt_s = rep_c.summary()["checkpoint_s_total"]

    # --- crash -> resume fast-forward -------------------------------------
    with tempfile.TemporaryDirectory() as d:
        pol = res_mod.CheckpointPolicy(d, every_segments=2, blocking=True)
        plan = res_mod.FaultPlan(
            (res_mod.Fault("crash", shard=0, segment=6),))
        try:
            dist_mod.run_sweep(spec, cache, timing, stream_chunk=seg,
                               resume=pol, fault_plan=plan)
            raise AssertionError("injected crash did not fire")
        except res_mod.RunKilled:
            pass
        rep_r = res_mod.RunReport()
        t0 = time.time()
        rows_r = dist_mod.run_sweep(spec, cache, timing, stream_chunk=seg,
                                    resume=pol, report=rep_r)
        t_resume = time.time() - t0
    resume_parity = rows_r == base_rows
    assert resume_parity, "resumed rows diverged from the plain sweep"
    ff = rep_r.summary()["fast_forwarded_segments"]

    # --- transient retry with backoff -------------------------------------
    plan = res_mod.FaultPlan(
        (res_mod.Fault("transient", shard=0, segment=0, count=2),))
    rep_t = res_mod.RunReport()
    rows_t = dist_mod.run_sweep(
        spec, cache, timing, stream_chunk=seg, fault_plan=plan,
        retry=res_mod.RetryPolicy(backoff_s=0.001), report=rep_t)
    retry_parity = rows_t == base_rows
    assert retry_parity, "retried rows diverged from the plain sweep"
    retries = rep_t.retries

    report = {
        "suite": {"footprint_factors": [2],
                  "policies": [numa.describe(p_) for p_ in spec.policies],
                  "cpus": [c.kind for c in spec.cpus],
                  "rows": len(base_rows), "stream_chunk": seg,
                  "checkpoint_every_segments": 2},
        "plain_warm_s": round(t_plain, 4),
        "checkpointed_warm_s": round(t_ckpt, 4),
        "checkpoint_overhead_pct": round(overhead_pct, 2),
        "checkpoints_written": n_ckpts,
        "checkpoint_s_total": round(ckpt_s, 4),
        "checkpointed_bitwise_equal_plain": ckpt_parity,
        "resume": {
            "killed_at_segment": 6,
            "fast_forwarded_segments": ff,
            "resume_s": round(t_resume, 4),
            "rows_bitwise_equal_uninterrupted": resume_parity,
        },
        "retry": {
            "injected_transients": 2,
            "retries": retries,
            "rows_bitwise_equal_plain": retry_parity,
        },
    }
    out = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_resilience.json"
    out.write_text(json.dumps(report, indent=1) + "\n")
    print(f"  checkpointing: plain {t_plain:.3f}s -> checkpointed "
          f"{t_ckpt:.3f}s ({overhead_pct:+.1f}%, {n_ckpts} checkpoints, "
          f"{ckpt_s:.3f}s writing); parity={ckpt_parity}")
    print(f"  crash@seg6 -> resume: fast-forwarded {ff} segments, "
          f"resume {t_resume:.3f}s; parity={resume_parity}")
    print(f"  transient x2 -> {retries} retries absorbed; "
          f"parity={retry_parity} -> {out.name}")
    emit("resilience_ckpt", t_ckpt * 1e6,
         f"overhead={overhead_pct:.1f}%;parity={ckpt_parity}")
    emit("resilience_resume", t_resume * 1e6,
         f"ff_segments={ff};retries={retries}")


def fidelity() -> None:
    """Latency distributions, MSHR backpressure and the CXL-SSD tier.

    Part 1 sweeps the loaded-latency ("banana") curve per expander type
    — dram / cxl / ssd via `TimingConfig.loaded_latency_ns` — asserting
    each curve is monotone in offered load, collapses to its idle floor
    at zero load, and that the SSD's write path is slower than its read
    path (flash asymmetry through the internal DRAM cache).  It also
    shows MSHR backpressure: a small outstanding-request cap lengthens
    the converged runtime of the identical sweep.

    Part 2 runs one distribution-enabled grid — topologies (direct1,
    direct2+ssd) x tiering (static, three-tier dynamic) x distributions
    (off, dist(n=512)) — through the batched engine on both backends,
    asserting p50 <= p95 <= p99 on every distribution row, that the
    "off" rows are bitwise-equal to a sweep with no distributions axis
    (the legacy schema), that a zero queueing excess collapses every
    percentile to the deterministic fixed point, and that the pallas
    rows equal the reference rows.  Writes `BENCH_fidelity.json`.
    """
    from repro.core import tiering_dyn as td
    from repro.core.timing import LatencyDistribution
    from repro.workloads import HotCold

    print("\n== fidelity (latency distributions + MSHR + CXL-SSD) ==")
    timing = TimingConfig()

    # --- part 1: banana curve per expander type -------------------------
    curves = {}
    idle_floor = {"dram": timing.dram.idle_ns, "cxl": timing.cxl.idle_ns,
                  "ssd": timing.ssd.idle_read_ns}
    for kind in ("dram", "cxl", "ssd"):
        c = latency_bandwidth_curve(timing, kind, n=16)
        lat = c[:, 2]
        assert np.all(np.diff(lat) >= 0.0), \
            f"{kind} loaded latency must be monotone in offered load"
        zero = float(np.asarray(timing.loaded_latency_ns(kind, 0.0)))
        assert zero == idle_floor[kind], \
            f"{kind} zero-load latency {zero} != idle floor"
        curves[kind] = [[round(float(v), 3) for v in row] for row in c]
        print(f"  {kind:>4}: idle {idle_floor[kind]:7.1f} ns -> "
              f"{float(lat[-1]):8.1f} ns at {float(c[-1, 0]):.0f} GB/s "
              f"offered")
    ssd_rd = float(np.asarray(timing.ssd.loaded_latency_ns(0.0, 1.0)))
    ssd_wr = float(np.asarray(timing.ssd.loaded_latency_ns(0.0, 0.0)))
    assert ssd_wr > ssd_rd, "SSD write path must be slower than read"

    # zero queueing excess collapses every percentile to the fixed point
    dist = LatencyDistribution()
    for tid in range(4):
        flat = dist.latency_percentiles(idle_floor["cxl"],
                                        idle_floor["cxl"], tid)
        assert np.all(np.asarray(flat) == idle_floor["cxl"]), \
            "zero excess must collapse the distribution to the legacy point"

    # --- part 2: distribution-enabled sweep, both backends --------------
    cache = cache_mod.CacheParams(l1_bytes=16 * 1024, l1_ways=4,
                                  l2_bytes=32 * 1024, l2_ways=8)
    topos = (route_mod.direct(1, 16),
             route_mod.direct(2, 16, ssd_gib=16))
    tiers = (None,
             td.DynamicTiering(epoch_len=2048, budget=16, threshold=8,
                               cxl_capacity_pages=8))
    spec = engine_mod.SweepSpec(
        footprint_factors=(8,), policies=(numa.ZNuma(1.0),),
        cpus=(CPUModel(kind="o3", mlp=8),),
        workloads=(HotCold(hot_page_frac=0.25),),
        topologies=topos, tiering=tiers,
        distributions=(None, dist))
    run = lambda: engine_mod.run_sweep(spec, cache, timing)
    t0 = time.time()
    rows = run()
    t_cold = time.time() - t0
    t0 = time.time()
    rows = run()
    t_warm = time.time() - t0

    # "off" rows == the legacy schema, bitwise (same device program)
    base = engine_mod.run_sweep(
        dataclasses.replace(spec, distributions=()), cache, timing)
    off = [{k: v for k, v in r.items() if k != "distribution"}
           for r in rows if r["distribution"] == "off"]
    legacy_equal = off == base
    assert legacy_equal, \
        "distribution-off rows diverged from the no-distributions sweep"

    # every distribution row: p50 <= p95 <= p99 per target
    tail = {}
    n_pct = 0
    for r in rows:
        if r["distribution"] == "off":
            continue
        targets = sorted(k[len("lat_"):-len("_p50_ns")]
                         for k in r if k.endswith("_p50_ns"))
        assert targets, "distribution row carries no percentile columns"
        for t in targets:
            p50, p95, p99 = (r[f"lat_{t}_p{p}_ns"] for p in (50, 95, 99))
            assert p50 <= p95 <= p99, \
                f"percentiles not monotone for {t}: {p50}, {p95}, {p99}"
            n_pct += 1
            if r["topology"] == "direct2+ssd" and r["tiering"] != "static":
                tail[t] = round(p99 / p50, 3) if p50 > 0 else None

    # pallas backend: identical rows through the dynamic MESI kernel
    t0 = time.time()
    pal_rows = engine_mod.run_sweep(
        dataclasses.replace(spec, backend="pallas"), cache, timing)
    t_pal = time.time() - t0
    pallas_equal = pal_rows == rows
    assert pallas_equal, "pallas fidelity rows diverged from reference"

    # MSHR backpressure: a small cap can only lengthen the runtime
    capped = dataclasses.replace(
        timing, cxl=dataclasses.replace(timing.cxl, mshr=4))
    slow = engine_mod.run_sweep(
        dataclasses.replace(spec, distributions=()), cache, capped)
    mshr_slowdowns = [s["time_ns"] / r["time_ns"]
                      for s, r in zip(slow, base) if r["time_ns"] > 0]
    assert all(x >= 1.0 for x in mshr_slowdowns), \
        "an MSHR cap must never speed a row up"
    assert max(mshr_slowdowns) > 1.0, \
        "a 4-entry CXL MSHR cap should throttle at least one row"

    ssd_tail = tail.get("ssd0")
    print(f"  sweep: {len(rows)} rows ({n_pct} percentile triples checked) "
          f"cold {t_cold:.2f}s warm {t_warm:.2f}s pallas {t_pal:.2f}s")
    print(f"  tails on direct2+ssd dynamic row (p99/p50): "
          + ", ".join(f"{k}={v}" for k, v in sorted(tail.items())))
    print(f"  mshr(cxl=4) slowdown: max {max(mshr_slowdowns):.3f}x")
    report = {
        "curves": curves,
        "idle_floor_ns": idle_floor,
        "ssd_idle_read_ns": ssd_rd,
        "ssd_idle_write_ns": ssd_wr,
        "distribution": dist.label,
        "cold_s": round(t_cold, 4),
        "warm_s": round(t_warm, 4),
        "pallas_s": round(t_pal, 4),
        "pallas_rows_bitwise_equal": pallas_equal,
        "off_rows_bitwise_equal_legacy": legacy_equal,
        "percentile_triples_checked": n_pct,
        "tail_p99_over_p50": tail,
        "mshr_cxl_cap": 4,
        "mshr_max_slowdown": round(max(mshr_slowdowns), 4),
        "rows": [{k: v for k, v in r.items() if k != "stats"}
                 for r in rows],
    }
    out = pathlib.Path(__file__).resolve().parent.parent \
        / "BENCH_fidelity.json"
    out.write_text(json.dumps(report, indent=1) + "\n")
    print(f"  p50<=p95<=p99 on all {n_pct} triples; off rows bitwise-"
          f"legacy; pallas parity -> {out.name}")
    emit("fidelity", t_warm * 1e6,
         f"tail_ssd={ssd_tail};pct_triples={n_pct};"
         f"mshr_slowdown={max(mshr_slowdowns):.3f}")


def roofline_summary() -> None:
    """Digest of the dry-run-derived roofline (experiments/roofline)."""
    print("\n== roofline_summary (from multi-pod dry-run) ==")
    path = pathlib.Path("experiments/roofline")
    for name in ("optimized.json", "baseline.json"):
        f = path / name
        if f.exists():
            rows = json.loads(f.read_text())
            break
    else:
        print("(run the dry-run sweep + `python -m repro.roofline.report`)")
        emit("roofline_summary", 0.0, "missing")
        return
    doms: Dict[str, int] = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    best = max(rows, key=lambda r: r["mfu_bound"])
    trains = [r for r in rows if r["shape"] == "train_4k"]
    med = sorted(r["mfu_bound"] for r in trains)[len(trains)//2] if trains \
        else 0.0
    print(f"[{name}] cells={len(rows)} dominant-term histogram={doms}")
    print(f"best MFU-bound: {best['arch']} {best['shape']} "
          f"{best['mfu_bound']:.1%}; median train MFU-bound {med:.1%}")
    emit("roofline_summary", 0.0,
         f"cells={len(rows)};best={best['mfu_bound']:.3f};"
         f"median_train={med:.3f}")


BENCHES: Dict[str, Callable[[], None]] = {
    "fig5_llc_missrate": fig5_llc_missrate,
    "interleave_sweep": interleave_sweep,
    "latency_bandwidth": latency_bandwidth,
    "programming_models": programming_models,
    "kv_tiering": kv_tiering,
    "kernels_micro": kernels_micro,
    "engine": engine,
    "topology": topology,
    "workloads": workloads,
    "tiering": tiering,
    "distribute": distribute,
    "sampling": sampling,
    "resilience": resilience,
    "fidelity": fidelity,
    "roofline_summary": roofline_summary,
}


def main() -> None:
    import argparse

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", default=None, metavar="SUITE[,SUITE...]",
        help="comma-separated subset of suites to run (default: all); "
             f"choices: {', '.join(BENCHES)}")
    args = ap.parse_args()
    if args.only:
        names = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = sorted(set(names) - set(BENCHES))
        if unknown:
            ap.error(f"unknown suite(s) {', '.join(unknown)}; "
                     f"choices: {', '.join(BENCHES)}")
    else:
        names = list(BENCHES)
    for name in names:
        BENCHES[name]()
    print("\nname,us_per_call,derived")
    for row in ROWS:
        print(row)


if __name__ == "__main__":
    main()

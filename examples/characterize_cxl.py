"""Calibration workflow (paper §V): fit the simulator's CXL path to
measured latency/bandwidth points from a real expander card, then verify
the fitted model reproduces the measurements.

Here the "measurements" come from a hidden ground-truth timing (standing in
for Intel MLC numbers against real hardware); the workflow is identical.

    PYTHONPATH=src python examples/characterize_cxl.py
"""
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.timing import (CXLTiming, TimingConfig, calibrate,
                               latency_bandwidth_curve)

use_compile_cache()

# --- "hardware": an x16 Gen5 card with a slow media controller -------------
hardware = CXLTiming(lanes=16, pcie_gen=5, backend_ns=160.0,
                     link_prop_ns=25.0, backend_gbps=52.0, service_ns=45.0)
loads = np.linspace(2.0, hardware.payload_gbps() * 0.92, 10)
measured = [(float(g), float(hardware.loaded_latency_ns(g))) for g in loads]
print("measured (GB/s -> ns):")
for g, ns in measured:
    print(f"  {g:6.1f} -> {ns:7.1f}")

# --- calibrate a default model to the measurements --------------------------
fitted = calibrate(measured, peak_gbps_hint=hardware.payload_gbps())
print(f"\nfitted idle: {fitted.idle_ns:.1f} ns "
      f"(hardware {hardware.idle_ns:.1f} ns)")
print(f"fitted peak: {fitted.payload_gbps():.1f} GB/s "
      f"(hardware {hardware.payload_gbps():.1f} GB/s)")

err = max(abs(float(fitted.loaded_latency_ns(g)) - ns) / ns
          for g, ns in measured)
print(f"max relative error across the curve: {err:.1%}")

# --- the calibrated TimingConfig is what every layer above consumes ---------
cfg = TimingConfig(cxl=fitted)
curve = latency_bandwidth_curve(cfg, "cxl", n=6)
print("\ncalibrated banana curve (offered GB/s, achieved, latency ns):")
for offered, achieved, lat in curve:
    print(f"  {offered:6.1f} {achieved:8.1f} {lat:8.1f}")

# --- characterize the calibrated card: the §IV grid as ONE device program ---
# The batched trace engine stacks every (footprint, policy) cell and runs
# the exact MESI cache model under a single vmapped scan; CPU models ride
# the vectorized timing fixed point on top.
from repro.core import cache as cache_mod
from repro.core import engine, numa
from repro.core.machine import CPUModel

spec = engine.SweepSpec(
    footprint_factors=(2, 4, 8),
    policies=(numa.ZNuma(0.0), numa.WeightedInterleave(1, 1),
              numa.ZNuma(1.0)),
    cpus=(CPUModel(kind="inorder", mlp=1), CPUModel(kind="o3", mlp=8)))
cache = cache_mod.CacheParams(l1_bytes=16 * 1024, l1_ways=4,
                              l2_bytes=64 * 1024, l2_ways=8)
rows = engine.run_sweep(spec, cache, cfg)
print(f"\nSTREAM triad on the calibrated card "
      f"({len(spec.sim_cells)} cells -> {len(rows)} rows, one device call):")
print(f"{'kxL2':>5} {'policy':>18} {'cpu':>8} {'bw_GB/s':>8} "
      f"{'lat_cxl_ns':>10} {'llc_miss':>9}")
for r in rows:
    print(f"{r['footprint_x_l2']:>5} {r['policy']:>18} {r['cpu']:>8} "
          f"{r['bw_total_gbps']:>8.2f} {r['lat_cxl_ns']:>10.1f} "
          f"{r['l2_miss_rate']:>9.3f}")

# --- topology exploration: how many cards, and where on the bus? ------------
# The same calibrated card, deployed three ways: one direct-attach, two
# interleaved under one host bridge, four pooled behind a CXL switch.  Each
# topology's HDM decoders are programmed + committed by the driver-equivalent
# enumeration pass and every access routes through them to a concrete
# endpoint; all three topologies still run as ONE vmapped device program.
from repro.core import route

topo_spec = engine.SweepSpec(
    footprint_factors=(4,),
    policies=(numa.ZNuma(1.0),),
    cpus=(CPUModel(kind="o3", mlp=8),),
    topologies=(route.direct(1), route.direct(2), route.switched(4)))
from repro.core.machine import per_target_bw_columns

topo_rows = engine.run_sweep(topo_spec, cache, cfg)
print(f"\nsame card, three topologies (per-target achieved GB/s):")
print(f"{'topology':>10} {'bw_cxl':>7} {'lat_cxl_ns':>10}  per-target")
for r in topo_rows:
    per = [f"{r[k]:.2f}" for k in per_target_bw_columns(r)]
    print(f"{r['topology']:>10} {r['bw_cxl_gbps']:>7.2f} "
          f"{r['lat_cxl_ns']:>10.1f}  [{', '.join(per)}]")

# --- beyond STREAM: the calibrated card under realistic workloads ------------
# The on-device generators of repro.workloads (docs/workloads.md): a
# dependent-load pointer chase (idle-latency probe — MLP collapses to 1, so
# the loaded latency IS the runtime), GUPS random updates, LLM KV-decode
# gathers recorded from the real paged-KV serving stack, and MoE
# expert-weight streaming.  Still ONE vmapped device program.
from repro.workloads import Gups, KVDecode, MoEStream, PointerChase

wl_spec = engine.SweepSpec(
    footprint_factors=(4,),
    policies=(numa.ZNuma(1.0),),
    cpus=(CPUModel(kind="o3", mlp=8),),
    workloads=(PointerChase(), Gups(), KVDecode(), MoEStream()))
wl_rows = engine.run_sweep(wl_spec, cache, cfg)
print(f"\nworkloads on the calibrated card (4x L2, CXL-bound):")
print(f"{'workload':>14} {'bw_GB/s':>8} {'bw_cxl':>7} {'lat_cxl_ns':>10} "
      f"{'llc_miss':>9}")
for r in wl_rows:
    print(f"{r['workload']:>14} {r['bw_total_gbps']:>8.2f} "
          f"{r['bw_cxl_gbps']:>7.2f} {r['lat_cxl_ns']:>10.1f} "
          f"{r['l2_miss_rate']:>9.3f}")

# --- cache pollution: what the CXL tenant does to a DRAM-resident one --------
from repro.workloads import pollution_probe

pol = pollution_probe(cache)
print(f"\nLLC pollution (DRAM-resident pointer-chase probe vs a CXL GUPS "
      f"burst):\n  clean miss rate {pol['probe_miss_rate_clean']:.3f} -> "
      f"polluted {pol['probe_miss_rate_polluted']:.3f} "
      f"(delta {pol['pollution_delta']:.3f})")

# --- dynamic tiering: what a TPP-style kernel daemon would recover -----------
# The `tiering` axis (docs/tiering.md) carries the page->tier map as scan
# state: per epoch, per-page access counters accumulate on device, the
# hottest CXL pages promote to DRAM (coldest DRAM pages demote under
# capacity pressure), and the migration traffic contends inside the same
# timing fixed point.  `None` rows are the static baseline — bitwise-equal
# to the rows above — and the whole axis still runs as ONE device program.
from repro.core.tiering_dyn import DynamicTiering
from repro.workloads import HotCold

tier_spec = engine.SweepSpec(
    footprint_factors=(8,),
    policies=(numa.ZNuma(1.0),),           # static bind: everything on CXL
    cpus=(CPUModel(kind="o3", mlp=8),),
    workloads=(HotCold(hot_page_frac=0.25),),
    tiering=(None, DynamicTiering(epoch_len=2048, budget=16, threshold=8)))
tier_rows = engine.run_sweep(tier_spec, cache, cfg)
print(f"\ndynamic tiering on the calibrated card (hot/cold workload, "
      f"static zNUMA vs TPP-style promotion):")
print(f"{'tiering':>22} {'time_ms':>8} {'bw_GB/s':>8} {'mig_GB/s':>9} "
      f"{'migrated':>9}  dram_frac per epoch")
for r in tier_rows:
    fr = r.get("epoch_dram_frac")
    fr_s = " ".join(f"{f:.2f}" for f in fr[:6]) if fr else "-"
    print(f"{r['tiering']:>22} {r['time_ns']/1e6:>8.2f} "
          f"{r['bw_total_gbps']:>8.2f} "
          f"{r.get('migration_gbps', 0.0):>9.2f} "
          f"{str(r.get('migrated_pages', '-')):>9}  {fr_s}")

# --- scale-out: the same grid, sharded + streamed ----------------------------
# The sweep executor (docs/scaling.md) is an execution strategy, not a
# model change: shard the batch rows across the device mesh (padding
# squares off ragged grids; on this 1-device host the shards serialize)
# and stream every trace through the scan carry in 4096-access segments
# — and the rows, dynamic-tiering columns included, stay bitwise-equal
# to the single-program sweep above.
import jax

from repro.core import distribute

dist_rows = distribute.run_sweep(tier_spec, cache, cfg, mesh=2,
                                 stream_chunk=4096)
assert dist_rows == tier_rows
print(f"\nsharded (2 shards) + streamed (4096-access segments) rerun: "
      f"{len(dist_rows)} rows bitwise-equal to the single-program sweep "
      f"on {len(jax.local_devices())} device(s)")

"""CXLRAMSim facade: build -> enumerate -> online -> characterize.

One object wires the whole paper together: topology + firmware + enumeration
(:mod:`.topology`), per-tier timing (:mod:`.timing`), the cache/tier machine
(:mod:`.machine`), placement policies (:mod:`.numa`) and STREAM workloads
(:mod:`.stream`).  The quickstart example and every benchmark drive this
class; the framework's tiering planner (:mod:`repro.memory.tiering`) reuses
its timing + map.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

from repro.core import cache as cache_sim
from repro.core import engine as engine_mod
from repro.core import numa as numa_mod
from repro.core import route as route_mod
from repro.core import stream as stream_mod
from repro.core import topology as topo
from repro.core.machine import CPUModel, Machine, RunResult
from repro.core.switch import SwitchConfig
from repro.core.timing import TimingConfig


@dataclasses.dataclass
class SimConfig:
    dram_gib: int = 16
    expander_gib: Sequence[int] = (16,)
    n_cores: int = 4
    cache: cache_sim.CacheParams = dataclasses.field(
        default_factory=cache_sim.CacheParams)
    timing: TimingConfig = dataclasses.field(default_factory=TimingConfig)
    cpu: CPUModel = dataclasses.field(default_factory=CPUModel)


class CXLRAMSim:
    """Full-system CXL memory-expander simulator (JAX-native)."""

    def __init__(self, config: SimConfig | None = None):
        self.config = config or SimConfig()
        self.system, self.map, self.cli = topo.build_default_system(
            dram_gib=self.config.dram_gib,
            expander_gib=tuple(self.config.expander_gib),
            n_cores=self.config.n_cores)
        self.machine = Machine(self.config.cache, self.config.timing,
                               self.config.cpu)
        self._onlined = False

    # ---- lifecycle (CXL-CLI flow) ----------------------------------------
    def online(self, mode: str = "znuma") -> List[Dict]:
        """Online every region (the `cxl create-region` + ndctl flow)."""
        for r in list(self.map.regions):
            self.cli.online_memory(r.name, mode=mode)
        self._onlined = True
        return self.cli.list_regions()

    def memdevs(self) -> List[Dict]:
        return self.cli.list_memdevs()

    def numastat(self) -> Dict[int, Dict]:
        return self.cli.numastat()

    # ---- routing ----------------------------------------------------------
    def route(self, switch: Optional[SwitchConfig] = None
              ) -> route_mod.RouteMap:
        """N-target route map over this system's committed HDM decoders.

        Target 0 = local DRAM, 1..K = this system's expander endpoints;
        pass a `SwitchConfig` to model all endpoints behind one switch.
        """
        return route_mod.build_route_from_system(
            self.map, self.config.timing, switch=switch)

    # ---- characterization -------------------------------------------------
    def _check_policy(self, policy: numa_mod.Policy) -> None:
        if not self._onlined and not isinstance(policy, numa_mod.ZNuma):
            raise RuntimeError("online() the CXL region first")

    def run_stream(self, kernel: str, footprint_bytes: int,
                   policy: numa_mod.Policy,
                   cpu: Optional[CPUModel] = None) -> RunResult:
        """One STREAM kernel pass through the cache/tier machine."""
        self._check_policy(policy)
        layout = stream_mod.layout_for_footprint(footprint_bytes)
        addr, is_write = stream_mod.stream_trace(kernel, layout)
        machine = self.machine if cpu is None else Machine(
            self.config.cache, self.config.timing, cpu)
        return machine.run_trace(addr, is_write, policy, layout.n_pages)

    def stream_suite(self, footprint_factors: Sequence[int] = (2, 4, 6, 8),
                     policy: Optional[numa_mod.Policy] = None,
                     kernel: str = "triad",
                     cpu: Optional[CPUModel] = None,
                     backend: Optional[str] = None,
                     topologies: Optional[Sequence[
                         route_mod.TopologySpec]] = None) -> List[Dict]:
        """The paper's §IV sweep: STREAM at k x L2 footprints.

        All footprints run as ONE batched device program (one compilation,
        one dispatch) through :mod:`repro.core.engine`; stats are
        bitwise-equal to :meth:`stream_suite_sequential`.  `topologies`
        adds the multi-expander axis: rows then carry per-target
        `bw_cxl{k}_gbps` / `lat_cxl{k}_ns` columns and a `topology` label.
        """
        policy = policy or numa_mod.ZNuma(cxl_fraction=1.0)
        return self.sweep(footprint_factors, policies=(policy,),
                          cpus=(cpu or self.config.cpu,), kernel=kernel,
                          backend=backend, topologies=topologies)

    def sweep(self, footprint_factors: Sequence[int] = (2, 4, 6, 8),
              policies: Optional[Sequence[numa_mod.Policy]] = None,
              cpus: Optional[Sequence[CPUModel]] = None,
              kernel: str = "triad",
              backend: Optional[str] = None,
              topologies: Optional[Sequence[route_mod.TopologySpec]] = None,
              workloads: Optional[Sequence] = None,
              tiering: Optional[Sequence] = None,
              sampling: Optional[Sequence] = None,
              distributions: Optional[Sequence] = None,
              mesh=None,
              stream_chunk: Optional[int] = None,
              resume=None,
              fault_plan=None,
              report=None) -> List[Dict]:
        """The full grid — (tiering x workload x topology x footprint x
        policy x CPU) — batched.

        Every (tiering, workload, topology, footprint, policy) cell is
        simulated in one vmapped device call; CPU models vary only the
        vectorized timing fixed point.  Without `topologies` the legacy
        binary DRAM/CXL path runs (bitwise-equal to a single
        direct-attach expander); without `workloads` the grid is the
        paper's STREAM suite.  Pass :mod:`repro.workloads` generators
        (pointer chase, GUPS, KV-decode, MoE streaming, hot/cold) to
        open the scenario axis — see ``docs/workloads.md`` — and
        :class:`repro.core.tiering_dyn.DynamicTiering` entries (``None``
        = static, bitwise-equal to today's rows) to sweep epoch-based
        hot-page promotion/demotion — see ``docs/tiering.md``.  Pass
        :class:`repro.core.sampling.SamplingSpec` entries (``None`` =
        exact, bitwise-equal to today's rows) to run SMARTS-style
        sampled simulation — detailed measurement windows scaled to
        whole-trace estimates with ``*_ci95`` confidence columns — see
        ``docs/sampling.md``.  Pass
        :class:`repro.core.timing.LatencyDistribution` entries (``None``
        = deterministic point timing, bitwise-equal to today's rows) to
        sweep queueing-derived latency *distributions* — rows gain
        per-target ``lat_<t>_p50/p95/p99_ns`` percentile columns — see
        ``docs/fidelity.md``.

        `mesh` shards the grid's batch rows across devices (a
        :class:`repro.core.distribute.Mesh` or an int shard count) and
        `stream_chunk` streams each trace through the scan carry in
        fixed-size segments (bounded device memory) — both execution
        strategies, never result changes: any mesh/chunk choice yields
        rows bitwise-equal to the defaults (``None``/``None`` = the
        single-program path).  See ``docs/scaling.md``.

        `resume` (a checkpoint directory or
        :class:`repro.core.resilience.CheckpointPolicy`), `fault_plan`
        (a :class:`repro.core.resilience.FaultPlan`) and `report` (a
        :class:`repro.core.resilience.RunReport` event sink) run the
        sweep through the fault-tolerant
        :class:`repro.core.distribute.ResilientExecutor`: carries
        checkpoint every N segments and a killed sweep rerun with the
        same `resume=` fast-forwards to where it died — with rows
        bitwise-identical to an uninterrupted run.  See
        ``docs/resilience.md``.
        """
        policies = tuple(policies) if policies else (
            numa_mod.ZNuma(cxl_fraction=1.0),)
        for p in policies:
            self._check_policy(p)
        cpus = tuple(cpus) if cpus else (self.config.cpu,)
        spec = engine_mod.SweepSpec(
            footprint_factors=tuple(footprint_factors), policies=policies,
            cpus=cpus, kernel=kernel, backend=backend,
            topologies=tuple(topologies) if topologies else (),
            workloads=tuple(workloads) if workloads else (),
            tiering=tuple(tiering) if tiering else (),
            sampling=tuple(sampling) if sampling else (),
            distributions=tuple(distributions) if distributions else ())
        if (mesh is None and stream_chunk is None and resume is None
                and fault_plan is None and report is None):
            return engine_mod.run_sweep(spec, self.config.cache,
                                        self.config.timing)
        from repro.core import distribute  # deferred: builds on engine
        return distribute.run_sweep(spec, self.config.cache,
                                    self.config.timing, mesh=mesh,
                                    stream_chunk=stream_chunk,
                                    resume=resume, fault_plan=fault_plan,
                                    report=report)

    def stream_suite_sequential(self,
                                footprint_factors: Sequence[int]
                                = (2, 4, 6, 8),
                                policy: Optional[numa_mod.Policy] = None,
                                kernel: str = "triad",
                                cpu: Optional[CPUModel] = None
                                ) -> List[Dict]:
        """Per-config sequential path (one dispatch + compile per footprint).

        Kept as the oracle/baseline the batched engine is tested and
        benchmarked against (`benchmarks/run.py --only engine`).
        """
        policy = policy or numa_mod.ZNuma(cxl_fraction=1.0)
        rows = []
        for k in footprint_factors:
            fp = k * self.config.cache.l2_bytes
            r = self.run_stream(kernel, fp, policy, cpu=cpu)
            rows.append({"footprint_x_l2": k, "kernel": kernel,
                         "policy": numa_mod.describe(policy),
                         "cpu": r.cpu, **r.row(), "stats": r.stats})
        return rows

    def latency_breakdown(self) -> Dict[str, float]:
        return self.config.timing.cxl.stage_breakdown()

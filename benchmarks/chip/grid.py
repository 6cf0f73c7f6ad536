"""A cell from its files: the benchmark's entry, configuration and traffic.

``load_cell`` finds everything by the names in ``BENCHMARK.json``: the
cell's configuration file (under ``configs/``), its traffic mix
(``traffic/<traffic>.json``) and its per-layer metrics.  ``simulator`` and
``sweep_grid`` turn a configuration and a traffic mix into the program's
objects; nothing in them names a cell, so a new cell is new files and
entries, not code.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Dict:
    """The cell, its configuration and traffic, and its per-layer metrics."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {
        "cell": cell,
        "config": json.loads((root / cfg_entry["file"]).read_text()),
        "traffic": json.loads(
            (HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


def workload_seed(traffic, seed: int) -> int:
    """The seed the cell's generator gets: ``--seed`` mod 2**32, unless
    the mix fixes one (a generator whose shapes change with its seed)."""
    return traffic["workload"].get("seed", seed % (1 << 32))


def simulator(cfg):
    """The program's simulator for a configuration file, onlined."""
    from repro.core import CXLRAMSim, SimConfig
    from repro.core.cache import CacheParams
    from repro.core.machine import CPUModel
    from repro.core.timing import CXLTiming, DramTiming, TimingConfig
    c, tm, topo = cfg["cache"], cfg["timing"], cfg["topology"]
    sim = CXLRAMSim(SimConfig(
        dram_gib=cfg["dram_gib"],
        expander_gib=(topo["expander_gib"],) * topo["expanders"],
        n_cores=c["cores"],
        cache=CacheParams(l1_bytes=c["l1_bytes"], l1_ways=c["l1_ways"],
                          l2_bytes=c["l2_bytes"], l2_ways=c["l2_ways"],
                          line_bytes=c["line_bytes"], cores=c["cores"]),
        timing=TimingConfig(dram=DramTiming(**tm["dram"]),
                            cxl=CXLTiming(**tm["cxl"])),
        cpu=CPUModel(**cfg["cpu"])))
    sim.online("znuma")
    return sim


def _topology(topo):
    from repro.core import route
    from repro.core.switch import SwitchConfig
    if topo["kind"] == "direct":
        return route.direct(topo["expanders"], topo["expander_gib"])
    if topo["kind"] == "switched":
        return route.switched(topo["expanders"], topo["expander_gib"],
                              SwitchConfig(n_downstream=topo["expanders"],
                                           **topo["switch"]))
    raise ValueError(f"unknown topology kind {topo['kind']!r}")


def _policy(pol):
    from repro.core import numa
    args = {k: v for k, v in pol.items() if k != "kind"}
    return getattr(numa, pol["kind"])(**args)


def sweep_grid(cfg, traffic, seed: int) -> Dict:
    """Keyword arguments of ``sim.sweep`` for this configuration and mix."""
    import repro.workloads as workloads
    from repro.core.tiering_dyn import DynamicTiering
    wl = traffic["workload"]
    grid = dict(
        footprint_factors=tuple(traffic["footprint_factors"]),
        policies=tuple(_policy(p) for p in traffic["policies"]),
        topologies=(_topology(cfg["topology"]),),
        workloads=(getattr(workloads, wl["kind"])(
            seed=workload_seed(traffic, seed), **wl.get("params", {})),))
    tiering: List = [None if t is None else DynamicTiering(**t)
                     for t in traffic.get("tiering", [])]
    if tiering:
        grid["tiering"] = tuple(tiering)
    return grid

"""What decides ``correct``: every row the window's sweeps returned,
against the plain reference (``reference.py``).

Four numbers are compared, each with its limit (``LIMITS``):

- ``rows_differ``: rows missing or extra, or whose labels or columns
  differ from the reference's.  Exact.
- ``counters_differ``: integer outputs that differ: every MESI counter of
  ``stats`` (per-target reads and writes, coherence counters) and the
  tierer's ``migrated_pages``.  Exact.
- ``epoch_frac_gap``: largest gap of a tiered row's per-epoch DRAM
  fractions (ratios of integer counts).  Exact.
- ``timing_rel_gap``: largest relative gap of a float column of the
  timing fixed point (``time_ns``, ``bw_*``, ``lat_*``,
  ``migration_gbps``, ``l2_miss_rate``).  Its limit lies between what
  sound runs read and what the float32 control reads; PERF.md gives
  both readings.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

LIMITS = {
    "rows_differ": 0,
    "counters_differ": 0,
    "epoch_frac_gap": 0.0,
    "timing_rel_gap": 1e-10,
}

LABELS = ("workload", "footprint_x_l2", "policy", "cpu", "topology",
          "tiering")
EXACT = ("stats", "migrated_pages", "epoch_dram_frac") + LABELS
BIG = 1e300           # stands for an unbounded gap in the printed JSON


def _rel(p: float, r: float) -> float:
    if p == r:
        return 0.0
    if not (math.isfinite(p) and math.isfinite(r)) or r == 0:
        return BIG
    return abs(p - r) / abs(r)


def compare(sweeps: Sequence[Sequence[Dict]], ref: Sequence[Dict]
            ) -> Dict[str, float]:
    """The compared numbers over every row of every sweep."""
    out = {"rows_differ": 0, "counters_differ": 0, "epoch_frac_gap": 0.0,
           "timing_rel_gap": 0.0}
    for rows in sweeps:
        out["rows_differ"] += abs(len(rows) - len(ref))
        for got, want in zip(rows, ref):
            if (set(got) != set(want)
                    or any(got.get(k) != want.get(k) for k in LABELS)):
                out["rows_differ"] += 1
            stats_g, stats_w = got.get("stats", {}), want["stats"]
            out["counters_differ"] += sum(
                stats_g.get(k) != v for k, v in stats_w.items())
            if "migrated_pages" in want:
                out["counters_differ"] += (
                    got.get("migrated_pages") != want["migrated_pages"])
                fg, fw = got.get("epoch_dram_frac") or [], \
                    want["epoch_dram_frac"]
                gap = max((abs(a - b) for a, b in zip(fg, fw)), default=0.0)
                if len(fg) != len(fw):
                    gap = BIG
                out["epoch_frac_gap"] = max(out["epoch_frac_gap"], gap)
            for k, v in want.items():
                if k in EXACT:
                    continue
                g = got.get(k)
                gap = (_rel(float(g), float(v))
                       if isinstance(g, (int, float)) else BIG)
                out["timing_rel_gap"] = max(out["timing_rel_gap"], gap)
    return out


def checks(values: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Each compared number beside its limit."""
    return {k: {"value": values[k], "limit": LIMITS[k]} for k in LIMITS}


def passed(values: Dict[str, float]) -> bool:
    return all(values[k] <= LIMITS[k] for k in LIMITS)


def sweeps_failed(sweeps: Sequence[Sequence[Dict]], ref: Sequence[Dict]
                  ) -> List[int]:
    """Indices of the sweeps that are not correct on their own."""
    return [i for i, rows in enumerate(sweeps)
            if not passed(compare([rows], ref))]

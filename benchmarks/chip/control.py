#!/usr/bin/env python3
"""Readings that the limits of ``compare.LIMITS`` are set from.

    python benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 ...

In one process, for each seed: one sweep of the cell's grid through the
program, compared with the plain reference (the lower readings: what
sound runs give), and the control compared with the same reference (the
upper readings).  The control is the reference put in the program's
place with its timing fixed point computed in float32, the precision
below the float64 the timing model states; it must come out as not
correct.  Prints one JSON line per seed and side.  Needs the chip, as a
benchmark run does; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def readings(name: str, seeds, *, root=ROOT, adjust=None):
    """Yield (seed, side, compared values, passed) for each seed."""
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(HERE))
    import numpy as np

    import compare
    import grid
    import reference
    loaded = grid.load_cell(name, root)
    if adjust is not None:
        adjust(loaded)
    cfg, traffic = loaded["config"], loaded["traffic"]
    sim = grid.simulator(cfg)
    for seed in seeds:
        rows = sim.sweep(**grid.sweep_grid(cfg, traffic, seed))
        wseed = grid.workload_seed(traffic, seed)
        ref = reference.sweep_rows(cfg, traffic, wseed)
        values = compare.compare([rows], ref)
        yield seed, "program", values, compare.passed(values)
        ctrl = reference.sweep_rows(cfg, traffic, wseed, ft=np.float32)
        values = compare.compare([ctrl], ref)
        yield seed, "control", values, compare.passed(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    for seed, side, values, ok in readings(args.workload, args.seeds):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "side": side, "correct": ok, **values}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python benchmarks/chip/run_cell.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/``) and a traffic mix (``traffic/``).  Set-up starts the
process, turns on the compile cache, builds the simulator
(``CXLRAMSim(SimConfig(...)).online("znuma")``) and runs one warm-up
sweep of the cell's own grid, which compiles every shape the window
uses.  The window then runs whole sweeps back to back and starts no new
one once ``--seconds`` have passed.  Every row of every sweep is then
compared with the plain reference (``reference.py``, ``compare.py``).

``--trace 0`` reports the end-to-end metrics (``setup_s``, ``sweep_s``);
``--trace 1`` runs the window under the profiler and reports the cell's
per-layer metrics (``metrics/<name>.py``), the device's busy and window
seconds and a breakdown of device time and idle gaps.

Exits 1 with no result line when JAX's first device is not a TPU, when
the host has fewer chips than the cell asks for, or when the device kind
has no entry in ``peaks.json``.  The last line of standard output is the
result, JSON; the compared numbers beside their limits are the last lines
of standard error and the result's last key.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import pathlib
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _process_start_wall() -> float:
    """Wall-clock time this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            after = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started = int(after[19]) / os.sysconf("SC_CLK_TCK")
        return time.time() - (uptime - started)
    except (OSError, ValueError, IndexError):
        return time.time()


START = _process_start_wall()


def _log(msg: str) -> None:
    print(msg, flush=True)


def _metric_module(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def why_not(devices, chips: int):
    """Why this host cannot run the cell, or None."""
    dev = devices[0]
    if dev.platform != "tpu":
        return f"no TPU: JAX's first device is {dev.platform}"
    if len(devices) < chips:
        return f"the cell asks for {chips} chips, {len(devices)} found"
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks:
        return f"device kind {dev.device_kind!r} is not in peaks.json"
    return None


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: pathlib.Path = ROOT, require_tpu: bool = True,
        adjust=None) -> dict:
    """One run of a cell; returns the result dict (``checks`` last).

    ``require_tpu=False`` and ``adjust`` (a function that edits the loaded
    cell, e.g. to a small cache) are for the harness's own tests on the
    CPU; a benchmark run uses neither.
    """
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(HERE))
    import grid
    import spans
    import tracing
    loaded = grid.load_cell(name, root)
    if adjust is not None:
        adjust(loaded)
    import jax
    devices = jax.devices()
    why = why_not(devices, loaded["cell"]["chips"])
    if why is not None and require_tpu:
        raise SystemExit(f"run_cell: {why}")
    dev = devices[0]
    compiles = spans.CompileEvents()
    cache = spans.CacheEvents()
    cfg, traffic = loaded["config"], loaded["traffic"]
    sim = grid.simulator(cfg)
    sweep = grid.sweep_grid(cfg, traffic, seed)
    warm_rows = sim.sweep(**sweep)
    warm_compiles = compiles.count
    _log(f"[{dev.platform} {dev.device_kind}] x{len(devices)} {name} "
         f"seed {seed}: set-up done, {cache}, {warm_compiles} compilations "
         f"or loads")

    layer = spans.LayerSpans()
    sweeps = []
    setup_s = time.time() - START
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tmp:
        prof = tracing.WindowProfiler(tmp) if trace else None
        layer.profiler = prof
        try:
            with layer.watch() if trace else contextlib.nullcontext():
                if prof is not None:
                    prof.start()
                t0 = time.perf_counter_ns()
                while True:
                    sweeps.append(sim.sweep(**sweep))
                    if time.perf_counter_ns() - t0 >= seconds * 1e9:
                        break
                t1 = time.perf_counter_ns()
        finally:
            if prof is not None:
                prof.stop()
        window_compiles = compiles.count - warm_compiles
        window_s = (t1 - t0) / 1e9
        result_trace = (_reduce(prof, loaded, layer, (t0, t1), len(sweeps),
                                window_compiles) if trace else None)
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    del sim
    accesses = sum(r["stats"]["l1_hit"] + r["stats"]["l1_miss"]
                   for r in warm_rows)
    sweep_s = window_s / len(sweeps)
    _log(f"{name}: {len(sweeps)} sweeps in {window_s:.3f} s, "
         f"{accesses} simulated accesses a sweep, "
         f"{accesses / sweep_s:.1f} accesses/s, {window_compiles} "
         f"compilations in the window, {cache}")

    import compare
    import reference
    t = time.perf_counter()
    ref = reference.sweep_rows(cfg, traffic,
                                 grid.workload_seed(traffic, seed))
    values = compare.compare(sweeps, ref)
    _log(f"{name}: reference took {time.perf_counter() - t:.1f} s")
    checks = compare.checks(values)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        metrics = result_trace["metrics"]
        device.update(busy_s=result_trace["busy_s"],
                      window_s=result_trace["window_s"])
    else:
        units = {m["name"]: m["unit"] for m in loaded["end_to_end"]}
        metrics = {"setup_s": {"value": setup_s, "unit": units["setup_s"]},
                   "sweep_s": {"value": sweep_s, "unit": units["sweep_s"]}}
    out = {"correct": compare.passed(values), "attempted": len(sweeps),
           "failed": len(compare.sweeps_failed(sweeps, ref)),
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = result_trace["breakdown"]
    out["checks"] = checks
    return out


def _reduce(prof, loaded, layer, window, n_sweeps, window_compiles) -> dict:
    """Reduce the window's profiler sessions, read the cell's metrics."""
    import reduce
    sessions = [reduce.load(path, anchor) for path, anchor in prof.sessions]
    ctx = reduce.Context(sessions, layer.programs, layer.spans, window,
                         prof.paused, dict(layer.seconds),
                         dict(layer.row_steps), n_sweeps, window_compiles)
    metrics = {}
    for m in loaded["per_layer"]:
        value = _metric_module(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return {"metrics": metrics, "busy_s": ctx.busy_s,
            "window_s": ctx.window_s, "breakdown": ctx.breakdown()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache lives inside the checkout, at a fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as exc:
        print(f"run_cell: the program is missing: {exc}", file=sys.stderr)
        return 1
    import jax
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except SystemExit as exc:
        print(exc, file=sys.stderr)
        return 1
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

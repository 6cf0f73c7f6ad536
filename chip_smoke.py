#!/usr/bin/env python3
"""Drive the sweep engine once on a TPU through the public API and check
what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded sweeps against one chip

One chip: the paper's Table-I host (4 cores, 64 KiB 8-way L1, 2 MiB
16-way L2, 16 GiB DRAM, one 16 GiB expander) runs the smoke's two
sweeps.  The mixed sweep covers footprints x policies x topologies x
workloads x static/dynamic tiering; a grid that holds a dynamic tiering
runs every row, static ones included, through the epoch program.  The
static sweep (no tiering axis) runs the static program over a batch.
On a TPU each program runs its compiled Pallas kernel.  Each sweep is then
streamed through the resilient executor (rows bitwise equal, no retry,
degradation or eviction).  Every golden family must reproduce its
committed row, and one static and one dynamic full-width row must match
the same call pinned to the CPU (the reference scan), counter for
counter.  The static sweep and a dynamic-tiering sweep on
``backend="pallas"`` must give the rows of ``backend="reference"``.

Four chips: only the two sweeps sharded over four devices against the
same sweeps on one device; rows bitwise equal, and every chip ran a
shard.  The sharded static sweep pmaps the Pallas segment kernel, one
row a chip; the mixed sweep places its epoch-program shards round-robin.

The program's span recorder (``repro.core.obs``) is on throughout; its
counters give the compile-cache hits and misses the lines report, and
say which program and backend each sweep ran: the mixed sweep the epoch
program, the static sweep the static program, each on its Pallas
kernel.

Every line but the last records the run on the device it names and
claims nothing.  The last line is ``{"ok": true, "device": {...}}``; any
failure exits non-zero without it.  Runs in one process: a chip belongs
to one process at a time.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
STREAM_CHUNK = 65536

# Cuts from the full smoke grid (footprints (2, 8); STREAM triad, pointer
# chase, GUPS, KV decode).  They date from when the mixed grid ran the
# epoch program on the reference scan, at about 60 us a step per batch
# row on one v5e, where the full grid's 120 device rows x 2,097,150
# steps took about 4.4 hours per sweep.  The cut grid keeps 6 device
# rows x 155,648 steps.
CUTS = (
    "footprint 8 x L2",
    "STREAM triad, pointer chase and GUPS: each policy is a row of its "
    "own; KV decode owns its residency map, so its policies share a row",
)


def _label(dev) -> str:
    return f"[{dev.platform} {dev.device_kind}]"


def _mixed_grid():
    """The smoke's sweep: KV decode, static and dynamic tiering."""
    from repro.core import numa
    from repro.core import route as route_mod
    from repro.core.tiering_dyn import DynamicTiering
    from repro.workloads import KVDecode
    return dict(
        footprint_factors=(2,),
        policies=(numa.ZNuma(0.0), numa.WeightedInterleave(1, 1),
                  numa.ZNuma(1.0)),
        topologies=(route_mod.direct(1), route_mod.direct(2),
                    route_mod.switched(4)),
        workloads=(KVDecode(),))


def _static_grid():
    """Pointer chase over the two multi-target topologies and two
    policies: four batch rows of 131,072 accesses, one per chip when
    sharded four ways."""
    from repro.core import numa
    from repro.core import route as route_mod
    from repro.workloads import PointerChase
    return dict(
        footprint_factors=(2,),
        policies=(numa.WeightedInterleave(1, 1), numa.ZNuma(1.0)),
        topologies=(route_mod.direct(2), route_mod.switched(4)),
        workloads=(PointerChase(),))


def _sweeps():
    from repro.core.tiering_dyn import DynamicTiering
    return (("mixed", dict(_mixed_grid(),
                           tiering=(None, DynamicTiering()))),
            ("static", _static_grid()))


def _simulator():
    from repro.core import CXLRAMSim, SimConfig
    from repro.core.cache import CacheParams
    sim = CXLRAMSim(SimConfig(cache=CacheParams(cores=4)))
    sim.online("znuma")
    return sim


class DeviceRows:
    """Counts, per device, the batch rows whose counters a device call
    left non-zero: the rows that device simulated (padding rows stay
    zero).  Wraps the calls every executor's device work goes through:
    the engine's resident static scan, the sharded static step and the
    epoch program."""

    def __init__(self):
        self.rows = collections.Counter()

    def record(self, stats) -> None:
        import numpy as np
        for shard in stats.addressable_shards:
            data = np.asarray(shard.data).reshape(-1, stats.shape[-1])
            self.rows[shard.device.id] += int((data != 0).any(-1).sum())

    @property
    def accesses(self) -> int:
        return self._accesses

    @contextlib.contextmanager
    def watch(self):
        from repro.core import distribute, engine, tiering_dyn
        self.rows.clear()
        self._accesses = 0
        wrapped = ((engine, "run_traces", lambda out: out[0]),
                   (distribute, "_pmap_segment", lambda out: out[2]),
                   (tiering_dyn, "run_dynamic", lambda out: out.stats))
        originals = [getattr(mod, name) for mod, name, _ in wrapped]

        def spy(fn, stats_of):
            def call(*args, **kw):
                out = fn(*args, **kw)
                stats = stats_of(out)
                self.record(stats)
                # counters 0 and 1 are L1 hits and misses
                self._accesses += int(stats[..., :2].sum())
                return out
            return call

        for (mod, name, stats_of), fn in zip(wrapped, originals):
            setattr(mod, name, spy(fn, stats_of))
        try:
            yield self
        finally:
            for (mod, name, _), fn in zip(wrapped, originals):
                setattr(mod, name, fn)


def _cache_events() -> str:
    """Hits and misses of JAX's persistent compilation cache, as the
    program's recorder (``repro.core.obs``) counted them."""
    from repro.core import obs
    seen = obs.totals()
    return (f"compile cache {seen.get('cache_hits', 0)} hits, "
            f"{seen.get('cache_misses', 0)} misses so far")


def _last_program() -> str:
    """Which program ran last and on which backend, as the newest
    ``sweep.program`` span of the program's recorder names them."""
    from repro.core import obs
    rec = [r for r in obs.records() if r.name == "sweep.program"][-1]
    return f"{rec.counters['program']} program on {rec.counters['backend']}"


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _timed(fn):
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t


def one_chip(dev) -> None:
    import jax

    from repro.core.resilience import RunReport
    tag = _label(dev)
    sim = _simulator()
    spy = DeviceRows()
    for name, grid in _sweeps():
        with spy.watch():
            rows, cold = _timed(lambda: sim.sweep(**grid))
        acc = spy.accesses
        ran = _last_program()
        want = {"mixed": "epoch program on pallas",
                "static": "static program on pallas"}[name]
        _check(ran == want, f"{name} sweep: ran the {ran}, not the {want}")
        print(f"{tag} {name} sweep ({ran}): {len(rows)} rows in "
              f"{sum(spy.rows.values())} device rows, {acc} simulated "
              f"accesses, cold {cold:.1f} s (compile included); "
              f"{_cache_events()}", flush=True)
        if name == "mixed":
            again, warm = _timed(lambda: sim.sweep(**grid))
            _check(again == rows, "a repeated sweep changed its rows")
            print(f"{tag} {name} sweep again: rows bitwise equal, warm "
                  f"{warm:.1f} s, {acc / warm / 1e6:.4f} Maccess/s",
                  flush=True)

        report = RunReport()
        streamed, secs = _timed(lambda: sim.sweep(
            **grid, stream_chunk=STREAM_CHUNK, report=report))
        summary = report.summary()
        _check(streamed == rows,
               f"{name}: streamed rows differ from resident rows")
        bad = {k: summary[k] for k in ("retries", "degradations",
                                       "evictions")}
        _check(not any(bad.values()),
               f"{name}: recovery events in a clean run: {bad}")
        print(f"{tag} {name} sweep streamed ({STREAM_CHUNK}-access "
              f"segments, resilient executor): rows bitwise equal, {bad}, "
              f"{secs:.1f} s", flush=True)

    sys.path.insert(0, str(ROOT / "tests"))
    import test_golden_stats as golden
    for family, case in sorted(golden.GOLDEN_CASES.items()):
        want = json.loads((golden.GOLDEN_DIR / f"{family}.json").read_text())
        got, secs = _timed(lambda: json.loads(json.dumps(case())))
        _check(got == want, f"golden family {family!r} drifted on the chip")
        print(f"{tag} golden {family}: bitwise equal ({secs:.1f} s)",
              flush=True)

    from repro.core import numa
    from repro.core import route as route_mod
    from repro.core.tiering_dyn import DynamicTiering
    from repro.workloads import PointerChase
    cpu = jax.devices("cpu")[0]
    for program, tiering in (("static", ()),
                             ("dynamic", (DynamicTiering(),))):
        def call(tiering=tiering):
            return sim.sweep((2,), policies=(numa.WeightedInterleave(1, 1),),
                             topologies=(route_mod.direct(2),),
                             workloads=(PointerChase(),), tiering=tiering)
        (row,), secs = _timed(call)
        with jax.default_device(cpu):
            (ref,), cpu_secs = _timed(call)
        _check(row["stats"] == ref["stats"],
               f"{program} row: chip and CPU counters differ")
        print(f"{tag} {program} row {row['workload']}: "
              f"{len(row['stats'])} counters bitwise equal to "
              f"{_label(cpu)} ({secs:.1f} s on the chip, {cpu_secs:.1f} s "
              f"on the CPU)", flush=True)

    static = _static_grid()
    pal, pal_secs = _timed(lambda: sim.sweep(**static, backend="pallas"))
    ref, ref_secs = _timed(lambda: sim.sweep(**static, backend="reference"))
    _check(pal == ref, "static sweep: pallas rows differ from reference")
    print(f"{tag} static sweep, backend='pallas' ({pal_secs:.1f} s): rows "
          f"bitwise equal to backend='reference' ({ref_secs:.1f} s)",
          flush=True)
    dynamic = dict(footprint_factors=(2,), tiering=(DynamicTiering(),))
    pal, pal_secs = _timed(lambda: sim.sweep(**dynamic, backend="pallas"))
    ref, ref_secs = _timed(lambda: sim.sweep(**dynamic,
                                             backend="reference"))
    _check(pal == ref, "dynamic sweep: pallas rows differ from reference")
    print(f"{tag} dynamic-tiering sweep, backend='pallas' ({pal_secs:.1f} "
          f"s): rows bitwise equal to backend='reference' ({ref_secs:.1f} "
          f"s)", flush=True)
    print(f"{tag} {_cache_events()}", flush=True)


def four_chips(devices) -> None:
    sim = _simulator()
    tag = f"{_label(devices[0])} x{len(devices)}"
    want = {d.id for d in devices}
    spy = DeviceRows()
    for name, grid in _sweeps():
        with spy.watch():
            sharded, secs = _timed(lambda: sim.sweep(**grid, mesh=4))
        per_device = dict(sorted(spy.rows.items()))
        with spy.watch():
            single, one_secs = _timed(lambda: sim.sweep(**grid))
        _check(single == sharded,
               f"{name}: sharded rows differ from one-device rows")
        _check(set(per_device) == want,
               f"{name}: shards ran on devices {sorted(per_device)}, not "
               f"{sorted(want)}")
        _check(sum(per_device.values()) == sum(spy.rows.values()),
               f"{name}: the shards simulated {per_device} rows, one "
               f"device {dict(spy.rows)}")
        _check(set(spy.rows) == {devices[0].id},
               f"{name}: mesh=None ran on {sorted(spy.rows)}")
        # four static rows make one real row per chip; the mixed grid's
        # six device rows shard 2/2/2/padding
        _check(name != "static" or all(per_device.values()),
               f"{name}: a device simulated no row: {per_device}")
        print(f"{tag}: {name} sweep, mesh=4 ({len(sharded)} rows, "
              f"{secs:.1f} s incl. compile) bitwise equal to mesh=None "
              f"({one_secs:.1f} s incl. compile); device rows simulated "
              f"per device {per_device}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the sharded sweeps on four chips")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    print(f"{_label(dev)} x{len(devices)}: compile cache "
          f"{use_compile_cache()}", flush=True)
    from repro.core import obs
    obs.enable()
    for cut in CUTS:
        print(f"{_label(dev)} grid cut: {cut}", flush=True)
    if args.chips == 4:
        four_chips(devices[:4])
    else:
        one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Time the sweep engine's device programs on a TPU, one phase a process.

    python tools/chip_probe.py static  [--batches 1 8 60 120]
    python tools/chip_probe.py dynamic [--batches 1 2]
    python tools/chip_probe.py donation

``static`` times one warm call of the static segment program
(``engine.run_batch_segment``) over 2,048 random accesses per row, from
random cores, at the paper's Table-I geometry (4 cores, 64 KiB 8-way
L1, 2 MiB 16-way L2, five route targets) for each batch width, once
at a small geometry (8 KiB 2-way L1, 16 KiB 8-way L2) at B=8, and at
one AMD EPYC 9004 CCD (8 cores, 32 KiB 8-way L1, a 32 MiB 16-way L3 as
the shared level, two targets) at B=1 and 2, on each backend (the
reference scan and the Pallas kernel); it prints
microseconds per scan step and whether the two backends' carries are
bitwise equal.  ``dynamic`` does the same for one 4,096-access epoch
slot of the epoch program (``tiering_dyn.run_dynamic_segment``, 1,026
pages, a migration at the slot's end), comparing the carries and the
per-slot outputs.
``donation`` says whether a segment call deletes its input carry with
``donate=False`` and with ``donate=True``.

Times are host-clock seconds around ``block_until_ready``: the first
call includes its compile, the second is warm.  Every line names the
device; without a TPU the probe exits non-zero.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

STEPS = 2048
SLOT = 4096            # DynamicTiering().epoch_len
PAGES = 1026           # pointer chase / GUPS at 2 x L2, plus padding


def _timed(fn):
    import jax
    t = time.perf_counter()
    jax.block_until_ready(fn())
    return time.perf_counter() - t


def _trace(rng, b, n, n_targets, cores=1, footprint=4 * 2 ** 20):
    import jax.numpy as jnp
    lines = footprint // 64            # 4 MiB: 2 x Table I's L2
    return (jnp.asarray(rng.integers(0, lines, (b, n)), jnp.int32),
            jnp.asarray(rng.integers(0, 2, (b, n)), jnp.int32),
            jnp.asarray(rng.integers(0, cores, (b, n)), jnp.int32),
            jnp.asarray(rng.integers(0, n_targets, (b, n)), jnp.int32))


def static(tag, rng, batches) -> None:
    import jax
    import numpy as np

    from repro.core import cache as cache_mod
    from repro.core import engine
    table1 = cache_mod.CacheParams(cores=4, n_targets=5)
    small = cache_mod.CacheParams(l1_bytes=8 * 1024, l1_ways=2,
                                  l2_bytes=16 * 1024, l2_ways=8,
                                  n_targets=5)
    genoa = cache_mod.CacheParams(cores=8, l1_bytes=32 * 1024,
                                  l2_bytes=32 * 2 ** 20, n_targets=2)
    for name, p, bs in (("Table I", table1, batches), ("small", small, (8,)),
                        ("Genoa CCD", genoa, (1, 2))):
        fp = 2 * max(p.l2_bytes, table1.l2_bytes)
        for b in bs:
            trace = _trace(rng, b, STEPS, p.n_targets, p.cores, fp)
            carry = engine.init_batch_carry(p, b)
            outs = []
            for backend in engine.BACKENDS:
                def call(backend=backend):
                    return engine.run_batch_segment(p, carry, *trace,
                                                    backend=backend)
                first, warm = _timed(call), _timed(call)
                outs.append(jax.device_get(call()))
                print(f"{tag} static segment, {name} geometry, B={b}, "
                      f"{backend}: first {first:.2f} s, warm {warm:.4f} s, "
                      f"{warm / STEPS * 1e6:.3f} us per step", flush=True)
            same = all(np.array_equal(x, y) for x, y in zip(*outs))
            print(f"{tag} static segment, {name} geometry, B={b}: carries "
                  f"bitwise equal across backends: {same}", flush=True)


def dynamic(tag, rng, batches) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import cache as cache_mod
    from repro.core import tiering_dyn
    p = cache_mod.CacheParams(cores=4, n_targets=5)
    for b in batches:
        trace = [x.reshape(b, 1, SLOT)
                 for x in _trace(rng, b, SLOT, p.n_targets)]
        ones = jnp.ones((b,), jnp.int32)
        # dyn_flag, n_pages, budget, threshold, period, dram_cap, ssd_tid,
        # cxl_cap, page_target_lines, s_warm, s_meas, s_per
        scalars = [ones, ones * PAGES, ones * 8, ones, ones, ones * (1 << 30),
                   ones * 0, ones * (1 << 30),
                   jnp.full((b, PAGES, p.n_targets), 16, jnp.int32),
                   ones * 0, ones * 0, ones * 0]
        carry = tiering_dyn.init_dyn_carry(p, jnp.ones((b, PAGES), jnp.int32))
        outs = []
        for backend in ("reference", "pallas"):
            def call(backend=backend):
                return tiering_dyn.run_dynamic_segment(
                    p, 8, SLOT + 1, carry, *trace, *scalars,
                    backend=backend)
            first, warm = _timed(call), _timed(call)
            outs.append(jax.tree_util.tree_leaves(jax.device_get(call())))
            print(f"{tag} epoch segment, Table I geometry, {PAGES} pages, "
                  f"B={b}, {backend}: first {first:.2f} s, warm {warm:.4f} s, "
                  f"{warm / SLOT * 1e6:.3f} us per step", flush=True)
        same = all(np.array_equal(x, y) for x, y in zip(*outs))
        print(f"{tag} epoch segment, Table I geometry, B={b}: carries and "
              f"per-slot outputs bitwise equal across backends: {same}",
              flush=True)


def donation(tag, rng, _batches) -> None:
    import jax

    from repro.core import cache as cache_mod
    from repro.core import engine
    p = cache_mod.CacheParams(cores=4, n_targets=5)
    trace = _trace(rng, 8, 512, p.n_targets)
    carry = engine.init_batch_carry(p, 8)
    for donate in (False, True):
        out = jax.block_until_ready(
            engine.run_batch_segment(p, carry, *trace, donate=donate,
                                     backend="reference"))
        print(f"{tag} segment call with donate={donate}: input carry "
              f"deleted {[x.is_deleted() for x in carry]}", flush=True)
        carry = out


PHASES = {"static": (static, (1, 8, 60, 120)),
          "dynamic": (dynamic, (1, 2)),
          "donation": (donation, ())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("phase", choices=sorted(PHASES))
    ap.add_argument("--batches", type=int, nargs="+",
                    help="batch widths (default: the phase's own)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_probe: no TPU (JAX's first device is {dev.platform})",
              file=sys.stderr)
        return 1
    fn, default = PHASES[args.phase]
    fn(f"[{dev.platform} {dev.device_kind}]",
       np.random.default_rng(args.seed), tuple(args.batches or default))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""CI crash/resume smoke: SIGKILL a real checkpointed sweep, resume it.

Unlike `tests/test_resilience.py` (which injects `RunKilled` in-process),
this gate kills an actual OS process mid-sweep — checkpoints must
survive an unclean death, including a kill that lands mid-write (the
manager's tmp-dir + rename protocol) — then resumes in the parent and
asserts the rows are bitwise-identical to an uninterrupted run:

    PYTHONPATH=src python tools/resilience_smoke.py

Flow: the parent spawns a child running the sweep with per-segment
checkpoints and a deliberate per-segment slowdown (so the kill window is
wide), waits for the first `step_*` directory to appear, SIGKILLs the
child, then computes the expected rows (plain streamed sweep) and
resumes from the checkpoint directory.  A child that finishes before
the kill lands degrades to a pure fast-forward resume — still a pass
(the parity assertion is identical).  The parent imports nothing of JAX
until the child has exited: an accelerator belongs to one process at a
time.  See docs/resilience.md.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import signal
import subprocess
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
STREAM_CHUNK = 512        # 4096-access traces -> 8 segments
SEGMENT_DELAY_S = 0.25    # injected per-segment stall in the child


def _sim_inputs():
    from repro.core import cache as cache_mod
    from repro.core import engine, numa
    from repro.core.machine import CPUModel
    from repro.core.timing import TimingConfig

    cache = cache_mod.CacheParams(l1_bytes=8 * 1024, l1_ways=2,
                                  l2_bytes=16 * 1024, l2_ways=8)
    spec = engine.SweepSpec(
        footprint_factors=(2,),
        policies=(numa.WeightedInterleave(1, 1), numa.ZNuma(1.0)),
        cpus=(CPUModel(kind="o3", mlp=8),))
    return spec, cache, TimingConfig()


def _policy(ckdir: str):
    from repro.core.resilience import CheckpointPolicy
    return CheckpointPolicy(ckdir, every_segments=1, blocking=True)


def child_main(ckdir: str) -> int:
    """Run the checkpointed sweep, stalling each segment (kill window)."""
    from repro.core import distribute
    from repro.core.resilience import Fault, FaultPlan

    spec, cache, timing = _sim_inputs()
    plan = FaultPlan(tuple(
        Fault("slow", shard=s, delay_s=SEGMENT_DELAY_S) for s in (0,)))
    distribute.run_sweep(spec, cache, timing, stream_chunk=STREAM_CHUNK,
                         resume=_policy(ckdir), fault_plan=plan)
    return 0


def _first_checkpoint(ckdir: pathlib.Path):
    return next(ckdir.glob("shard_*/step_*"), None)


def parent_main() -> int:
    with tempfile.TemporaryDirectory() as d:
        ckdir = pathlib.Path(d)
        child = subprocess.Popen(
            [sys.executable, __file__, "--child", d],
            env={**os.environ, "PYTHONPATH": str(ROOT / "src")},
            cwd=str(ROOT))
        killed = False
        deadline = time.time() + 120
        while time.time() < deadline:
            if _first_checkpoint(ckdir) is not None and child.poll() is None:
                time.sleep(0.2)     # let the kill land mid-segment
                child.send_signal(signal.SIGKILL)
                killed = True
                break
            if child.poll() is not None:
                break               # finished early: pure fast-forward below
            time.sleep(0.05)
        rc = child.wait(timeout=60)
        if not killed and rc != 0:
            print(f"child failed (rc={rc}) before any checkpoint appeared",
                  file=sys.stderr)
            return 1
        print(f"child {'SIGKILLed mid-sweep' if killed else 'finished'} "
              f"(rc={rc}); checkpoints present: "
              f"{sorted(p.name for p in ckdir.glob('shard_*/step_*'))}")

        from repro.core import distribute
        from repro.core.resilience import RunReport

        spec, cache, timing = _sim_inputs()
        expected = distribute.run_sweep(spec, cache, timing,
                                        stream_chunk=STREAM_CHUNK)
        report = RunReport()
        resumed = distribute.run_sweep(spec, cache, timing,
                                       stream_chunk=STREAM_CHUNK,
                                       resume=_policy(d), report=report)

    if resumed != expected:
        print("FAIL: resumed rows differ from the uninterrupted run",
              file=sys.stderr)
        return 1
    summary = report.summary()
    print(f"resume summary: {json.dumps(summary, sort_keys=True)}")
    print(f"OK: killed-and-resumed sweep is bitwise-identical to the "
          f"uninterrupted run ({len(resumed)} rows, "
          f"{summary['fast_forwarded_segments']} segments fast-forwarded)")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", metavar="CKPT_DIR", default=None,
                    help="(internal) run the to-be-killed sweep")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    if args.child:
        return child_main(args.child)
    return parent_main()


if __name__ == "__main__":
    sys.exit(main())

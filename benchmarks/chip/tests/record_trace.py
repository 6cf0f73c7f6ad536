#!/usr/bin/env python3
"""Record the small profiler session that ``test_reduce.py`` reads.

    python benchmarks/chip/tests/record_trace.py

On a TPU: one session, opened the way ``tracing.WindowProfiler`` opens
one, around a few small device programs with host gaps between them and
a ``bench.build`` span.  Writes ``data/session.xplane.pb`` and
``data/session.json`` (the anchor's host-clock time and the spans).
"""
import json
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    import glob

    import jax
    import jax.numpy as jnp

    import tracing
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    spans = []
    with tempfile.TemporaryDirectory() as tmp:
        prof = tracing.WindowProfiler(tmp)
        prof.start()
        lo = time.perf_counter_ns()
        for _ in range(3):
            t = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("bench.build"):
                time.sleep(0.01)
            spans.append((t, time.perf_counter_ns(), "bench.build"))
            f(x).block_until_ready()
        hi = time.perf_counter_ns()
        prof.stop()
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        shutil.copy(path, HERE / "data" / "session.xplane.pb")
    (HERE / "data" / "session.json").write_text(json.dumps(
        {"anchor_ns": prof.sessions[0][1], "window": [lo, hi],
         "spans": spans, "paused": prof.paused}, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

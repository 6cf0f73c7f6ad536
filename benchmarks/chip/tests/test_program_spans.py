"""The program's own spans (``repro.core.obs``) on the benchmark's clock.

A profiler session is put on the host clock through its ``bench.anchor``
annotation (``tracing.WindowProfiler``, ``reduce``).  The recorder
stamps its spans with the same clock, ``time.perf_counter_ns``, and
opens a ``TraceAnnotation`` of each: after the anchor shift, each
annotation starts within a millisecond of the recorder's own start.
"""
import glob
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))

import reduce  # noqa: E402


def _host_starts(path):
    """Start times of the session's host events, by name."""
    import jax
    pd = jax.profiler.ProfileData.from_file(
        glob.glob(f"{path}/**/*.xplane.pb", recursive=True)[0])
    host = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(e.start_ns)
    return host


def test_recorder_annotations_sit_on_the_recorder_clock(tmp_path):
    import jax.numpy as jnp

    import tracing
    from repro.core import obs
    x = jnp.ones((64,), jnp.float32)
    (x + 1).block_until_ready()
    prof = tracing.WindowProfiler(str(tmp_path))
    prof.start()
    obs.enable()
    try:
        with obs.span("sweep"):
            with obs.span("sweep.build"):
                time.sleep(0.003)
            with obs.span("sweep.program") as sp:
                sp.ready(x * 2 + 1)
            with obs.span("sweep.timing"):
                time.sleep(0.002)
    finally:
        obs.disable()
        prof.stop()
    recs = obs.records()
    (path, anchor_ns), = prof.sessions
    host = _host_starts(path)
    shift = anchor_ns - host[reduce.ANCHOR][0]
    assert [r.name for r in recs] == ["sweep", "sweep.build",
                                      "sweep.program", "sweep.timing"]
    for r in recs:
        (start,) = host[r.name]
        assert abs(start + shift - r.start_ns) < 1_000_000, r.name

"""Host seconds per sweep in grid build and trace generation.

Layer: ``engine.build_sweep_batch`` / ``engine.build_tiering_batch``
(routing and ``Workload.device_trace``), wrapped by the benchmark,
blocked on what they return and marked ``bench.build``.  Moves
``sweep_s``.
"""
SPAN = "bench.build"


def read(ctx):
    seconds = ctx.host_seconds.get(SPAN)
    if not seconds or not ctx.sweeps:
        return None
    return seconds / ctx.sweeps

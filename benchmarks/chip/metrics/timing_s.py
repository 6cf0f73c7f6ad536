"""Host seconds per sweep in the timing fixed point.

Layer: ``machine.time_batch`` (NumPy Picard iteration), called by the
engine as ``engine.time_batch``, wrapped by the benchmark and marked
``bench.timing``.  Moves ``sweep_s``.
"""
SPAN = "bench.timing"


def read(ctx):
    seconds = ctx.host_seconds.get(SPAN)
    if not seconds or not ctx.sweeps:
        return None
    return seconds / ctx.sweeps

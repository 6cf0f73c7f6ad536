"""Seconds per sweep outside the static program, in the server-LLC cell.

Layer: the host around the device program: grid build and trace
generation, the timing fixed point, row assembly and the gaps between
sweeps.  The traced window (less the profiler's own stops and starts)
less the benchmark's intervals of ``engine.run_traces``, over the
sweeps, so that ``sweep_s`` is about ``llc_step_us`` times the row-steps
of a sweep plus this.  Moves ``sweep_s``.
"""
PROGRAM = "engine.run_traces"


def read(ctx):
    seconds = ctx.program_seconds(PROGRAM)
    if not seconds or not ctx.sweeps:
        return None
    return (ctx.window_s - seconds) / ctx.sweeps

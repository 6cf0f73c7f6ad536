"""Seconds of the static program per row-step, in microseconds.

Layer: static segment program (``engine._run_batch_reference`` over
``_run_batch_segment_impl``, a vmapped scan of ``cache._packed_step``).
The program runs as one device call per sweep from ``engine.run_traces``;
``spans.LayerSpans`` times each call on the host clock from dispatch to
completion (the profiler is paused meanwhile: traced op by op, one
sweep's scan writes millions of events) and counts its row-steps, batch
rows times padded scan steps.  Moves ``sweep_s``.  After a rename of the
function this reads nothing.
"""
PROGRAM = "engine.run_traces"


def read(ctx):
    seconds = ctx.program_seconds(PROGRAM)
    steps = ctx.row_steps.get(PROGRAM, 0)
    if not seconds or not steps:
        return None
    return seconds / steps * 1e6

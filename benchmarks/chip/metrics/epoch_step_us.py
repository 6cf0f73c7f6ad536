"""Seconds of the epoch program per row-step, in microseconds.

Layer: epoch segment program (``tiering_dyn._run_dynamic``: the slot
scan of ``_slot_step`` with ``_migration_step`` at each epoch boundary).
The program runs as one device call per sweep from ``tiering_dyn.run_dynamic``;
``spans.LayerSpans`` times each call on the host clock from dispatch to
completion (the profiler is paused meanwhile: traced op by op, one
sweep's scan writes millions of events) and counts its row-steps, batch
rows times padded scan steps.  Moves ``sweep_s``.  After a rename of the
function this reads nothing.
"""
PROGRAM = "tiering_dyn.run_dynamic"


def read(ctx):
    seconds = ctx.program_seconds(PROGRAM)
    steps = ctx.row_steps.get(PROGRAM, 0)
    if not seconds or not steps:
        return None
    return seconds / steps * 1e6

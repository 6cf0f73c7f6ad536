"""Seconds of the static program per row-step, in microseconds, in the
server-LLC cell.

Layer: static segment program, at a cache whose state outgrows Mosaic's
default scoped VMEM (on a TPU the Pallas kernel ``mesi_cache_sim``
compiled with the VMEM its blocks need, where the chip holds them; the
reference scan otherwise).  Read as ``static_step_us`` reads it: the
benchmark's interval of ``engine.run_traces``, dispatch to completion
(input preparation included), over its row-steps, batch rows times
padded scan steps.  Moves ``sweep_s``.
"""
PROGRAM = "engine.run_traces"


def read(ctx):
    seconds = ctx.program_seconds(PROGRAM)
    steps = ctx.row_steps.get(PROGRAM, 0)
    if not seconds or not steps:
        return None
    return seconds / steps * 1e6

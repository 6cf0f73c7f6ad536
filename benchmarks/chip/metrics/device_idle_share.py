"""Share of the traced window in which the device was not known busy.

Layer: the device.  Busy time is the union of two kinds of interval
(``reduce.Context``): the op events of the profiler's trace, outside the
long device programs, and each long program's interval on the host
clock, from its executor call's dispatch to its completion, when the
profiler is paused (traced op by op, one sweep writes millions of
events).  That host interval is over 99% of the busy time, so this is a
host-clock reading, and idle time inside an executor call (input
preparation, dispatch between segments) is not measured: it counts as
busy.  What it sees is idle time between executor calls: grid build,
trace generation, timing and row assembly.  In percent; moves
``sweep_s``.
"""


def read(ctx):
    share = ctx.idle_share()
    return None if share is None else 100.0 * share

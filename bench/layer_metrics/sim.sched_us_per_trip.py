"""sim.sched_us_per_trip: device time per trip of the step loop in the
scheduler: the ops of the named scope `sched` (the key split, the
choice of the next process by `argmin` over its ready times, the fault
test), in microseconds."""
from bench import program


def read(ctx):
    return program.scope_us_per_trip(ctx, "/sched/")

"""sim.recovery_us_per_trip: device time per trip of the step loop in
the recovery handlers: the ops of the named scope `recovery` (the
program's lease-based repairs, branches of the handler switch that
`vmap` runs in every lane), in microseconds. Only a loop that carries
fault plans has such ops."""
from bench import program


def read(ctx):
    return program.scope_us_per_trip(ctx, "/recovery/")

"""sim.handler_us_per_trip: device time per trip of the step loop in the
instruction handlers: the ops of the named scope `handlers` (the
`lax.switch` over the program's handlers, which `vmap` makes a select
over all of them), in microseconds."""
from bench import program


def read(ctx):
    return program.scope_us_per_trip(ctx, "/handlers/")

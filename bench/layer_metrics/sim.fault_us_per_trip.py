"""sim.fault_us_per_trip: device time per trip of the step loop in the
crash branch: the ops of the named scope `fault` (a crash or revive of
the scheduled process, which `vmap` runs in every lane beside the
instruction), in microseconds. Only a loop that carries fault plans has
such ops."""
from bench import program


def read(ctx):
    return program.scope_us_per_trip(ctx, "/fault/")

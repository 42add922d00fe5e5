"""setup.compile_s: seconds of XLA compiles in set-up, persistent-cache
loads included (JAX's `backend_compile_duration` events), as the
program files them under its `session.*` spans (counter
`jit.compile_s`)."""
from bench import program


def read(ctx):
    return program.setup_counter(ctx, "jit.compile_s")

"""setup.lower_s: seconds of lowering jaxprs to MLIR in set-up (JAX's
`jaxpr_to_mlir_module_duration` events), as the program files them
under its `session.*` spans (counter `jit.lower_s`)."""
from bench import program


def read(ctx):
    return program.setup_counter(ctx, "jit.lower_s")

"""setup.trace_s: seconds of tracing Python into jaxprs in set-up (JAX's
`jaxpr_trace_duration` events), as the program files them under its
`session.*` spans (counter `jit.trace_s`)."""
from bench import program


def read(ctx):
    return program.setup_counter(ctx, "jit.trace_s")

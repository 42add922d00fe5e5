"""setup.build_s: seconds of `Session` construction in set-up, the
program's `session.build` span (machine, layout and roles; program, env
and handlers; the initial state)."""
from bench import program


def read(ctx):
    return program.setup_seconds(ctx, "session.build")

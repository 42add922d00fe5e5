"""sim.dispatch_ms: host milliseconds of one `Session.run_batch` call in
the window, from its start until the program returns its Metrics
without waiting for them (the seeds' copy to the device and the
dispatch): the median over the window's calls of the program's
`session.run_batch` span."""
from bench import program


def read(ctx):
    return program.window_median_ms(ctx, "session.run_batch")

"""sim.idle_share: the share of the traced window in which no op ran on
the device, the mean over the devices of a simulator cell."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else trace.idle_share()

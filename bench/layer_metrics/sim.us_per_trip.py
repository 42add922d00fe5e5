"""sim.us_per_trip: device time per trip of the simulator's step loop.

A traced simulator run records a slice of one call. Every op of the
vmapped `while_loop`'s body runs once per trip, so a device's trips in
the slice are the count of its most frequent op there. The metric is
the device's busy time in the slice over those trips, in microseconds,
the mean over the devices.
"""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    pairs = [(b, t) for b, t in zip(trace.busy_s_by_device(), trace.trips())
             if t > 0]
    if not pairs:
        return None
    return sum(b / t for b, t in pairs) / len(pairs) * 1e6

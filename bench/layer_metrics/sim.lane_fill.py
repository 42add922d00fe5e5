"""sim.lane_fill: the share of lane-trips of the step loop that did work.

Each trip of a vmapped `while_loop` steps every lane of the batch,
whether that lane still has an event to run or has finished. Over the
window's calls: the events of the lanes, over lanes times trips (the
events of the call's longest lane). An exact count from the runs'
`events`.
"""


def read(ctx):
    driver = ctx["driver"]
    if not hasattr(driver, "lanes"):
        return None
    useful = capacity = 0
    for events in driver.lanes():
        useful += int(events.sum())
        capacity += events.size * int(events.max())
    return useful / capacity if capacity else None

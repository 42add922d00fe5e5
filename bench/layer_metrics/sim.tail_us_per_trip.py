"""sim.tail_us_per_trip: device time per trip of the step loop in the
instruction tail: the ops of the named scope `tail`
(`engine.apply_effect`, which applies the effect of the one instruction
the switch selected: jitter, occupancy, wake passes, the per-process
stores), in microseconds. A program whose handlers apply their own tail
has no such scope, and gives nothing."""
from bench import program

SCOPE = "/tail/"


def read(ctx):
    spans = program.spans_module()
    if spans is None or not any(SCOPE in s
                                for s in spans.op_scopes().values()):
        return None
    return program.scope_us_per_trip(ctx, SCOPE)

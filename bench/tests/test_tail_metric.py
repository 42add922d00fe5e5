"""The reader of the step loop's instruction tail, on a trace made by
hand, and on programs that have no `tail` scope."""
import pytest

from bench import program
from bench.harness import layer_reader
from bench.trace import DeviceOps, summarize
from repro.core import spans

SCOPES = {"fusion.1": "jit(f)/vmap()/while/body/sched/argmin",
          "fusion.2": "jit(f)/vmap()/while/body/handlers/pc.CS/add",
          "scatter.3": "jit(f)/vmap()/while/body/tail/scatter",
          "copy.4": "jit(f)/vmap()/while/body/copy"}


class FakeDriver:
    calls = [(None, None, 10.0, 11.0)]


def context(monkeypatch, scopes):
    monkeypatch.setattr(spans, "op_scopes", lambda: dict(scopes))
    ops = []
    for trip in range(4):
        t = 1000 * trip
        ops += [(t, t + 100, "fusion.1"), (t + 100, t + 400, "fusion.2"),
                (t + 400, t + 450, "scatter.3"), (t + 450, t + 600, "copy.4")]
    trace = summarize([DeviceOps("/device:TPU:0", ops)],
                      [(0, 4000, "bench.window")])
    return {"driver": FakeDriver(), "trace": trace}


def test_the_tail_is_its_scope_share_of_a_trip(monkeypatch):
    ctx = context(monkeypatch, SCOPES)
    tail = layer_reader("sim.tail_us_per_trip")(ctx)
    assert tail == pytest.approx(50e-3)            # 50 ns a trip, in us
    assert tail + layer_reader("sim.handler_us_per_trip")(ctx) <= \
        layer_reader("sim.us_per_trip")(ctx)


def test_no_tail_scope_gives_nothing(monkeypatch):
    no_tail = {op: s for op, s in SCOPES.items() if "/tail/" not in s}
    assert layer_reader("sim.tail_us_per_trip")(
        context(monkeypatch, no_tail)) is None


def test_no_program_spans_give_nothing(monkeypatch):
    ctx = context(monkeypatch, SCOPES)
    monkeypatch.setattr(program, "spans_module", lambda: None)
    assert layer_reader("sim.tail_us_per_trip")(ctx) is None

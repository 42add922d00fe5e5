"""Readers of the program's spans, counters and op scopes, on records
and traces made by hand, and on a program that has none."""
import pytest

from bench import program
from bench.harness import layer_reader
from bench.trace import DeviceOps, summarize
from repro.core import spans

READERS = ("setup.build_s", "setup.trace_s", "setup.lower_s",
           "setup.compile_s", "sim.dispatch_ms", "sim.sched_us_per_trip",
           "sim.handler_us_per_trip")


def record(name, start_ns, end_ns, parent=None, **counters):
    r = spans.Span(name)
    r.parent, r.start_ns, r.end_ns = parent, start_ns, end_ns
    r.counters = counters
    return r


class FakeDriver:
    def __init__(self, call_starts_s):
        self.calls = [(None, None, t, t + 1.0) for t in call_starts_s]


# Set-up from 0 to 10 s, a window of two calls from 10 s on.
RECORDS = [
    record("session.layout", 1_000_000_000, 1_100_000_000, "session.build"),
    record("session.init_state", 1_100_000_000, 1_300_000_000,
           "session.build", **{"jit.trace_s": 0.01, "jit.lower_s": 0.02,
                               "jit.compile_s": 0.05}),
    record("session.build", 1_000_000_000, 1_400_000_000),
    record("session.dispatch", 2_000_000_000, 8_000_000_000,
           "session.run_batch", **{"jit.trace_s": 3.0, "jit.lower_s": 2.0,
                                   "jit.compile_s": 1.5, "jit.traces": 7}),
    record("session.run_batch", 2_000_000_000, 9_000_000_000),
    record("unrelated", 9_000_000_000, 9_500_000_000,
           **{"jit.trace_s": 100.0}),
    record("session.run_batch", 10_000_000_000, 10_001_000_000),
    record("session.dispatch", 11_000_000_000, 11_002_000_000,
           "session.run_batch", **{"jit.trace_s": 50.0}),
    record("session.run_batch", 11_000_000_000, 11_003_000_000),
]
SCOPES = {"fusion.1": "jit(f)/vmap()/while/body/sched/argmin",
          "fusion.2": "jit(f)/vmap()/while/body/handlers/pc.CS/add",
          "select.3": "jit(f)/vmap()/while/body/handlers/select_n",
          "copy.4": "jit(f)/vmap()/while/body/copy"}


@pytest.fixture
def program_spans(monkeypatch):
    monkeypatch.setattr(spans, "records", lambda: list(RECORDS))
    monkeypatch.setattr(spans, "op_scopes", lambda: dict(SCOPES))
    ops = []
    for trip in range(4):
        t = 1000 * trip
        ops += [(t, t + 100, "fusion.1"), (t + 100, t + 400, "fusion.2"),
                (t + 400, t + 500, "select.3"), (t + 500, t + 600, "copy.4")]
    trace = summarize([DeviceOps("/device:TPU:0", ops)],
                      [(0, 4000, "bench.window")])
    return {"driver": FakeDriver([10.0, 11.0]), "trace": trace}


def test_set_up_readers_take_what_started_before_the_first_call(
        program_spans):
    ctx = program_spans
    assert layer_reader("setup.build_s")(ctx) == pytest.approx(0.4)
    # session.* spans in set-up only: not "unrelated", not the window.
    assert layer_reader("setup.trace_s")(ctx) == pytest.approx(3.01)
    assert layer_reader("setup.lower_s")(ctx) == pytest.approx(2.02)
    assert layer_reader("setup.compile_s")(ctx) == pytest.approx(1.55)


def test_dispatch_is_the_median_run_batch_of_the_window(program_spans):
    assert layer_reader("sim.dispatch_ms")(program_spans) == \
        pytest.approx(2.0)


def test_scope_readers_split_a_trip_by_named_scope(program_spans):
    ctx = program_spans
    sched = layer_reader("sim.sched_us_per_trip")(ctx)
    handlers = layer_reader("sim.handler_us_per_trip")(ctx)
    trip = layer_reader("sim.us_per_trip")(ctx)
    assert sched == pytest.approx(100e-3)          # 100 ns a trip, in us
    assert handlers == pytest.approx(400e-3)       # the handlers and select
    assert trip == pytest.approx(600e-3)
    assert sched + handlers <= trip


def test_readers_return_nothing_where_the_program_has_no_spans(
        program_spans, monkeypatch):
    monkeypatch.setattr(program, "spans_module", lambda: None)
    for name in READERS:
        assert layer_reader(name)(program_spans) is None


def test_readers_return_nothing_without_calls_or_a_trace():
    ctx = {"driver": FakeDriver([]), "trace": None}
    for name in READERS:
        assert layer_reader(name)(ctx) is None

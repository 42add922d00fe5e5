"""The program's host spans and counters (`repro.core.spans`), and the
named scopes of the step loop in the compiled program."""
import gc
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LockSpec, Session, engine, spans
from repro.core.programs import hier


def _last(name):
    return [r for r in spans.records() if r.name == name][-1]


def test_nesting_and_self_time():
    before = spans.totals().get("test.outer", spans.Total())
    with spans.span("test.outer"):
        with spans.span("test.inner"):
            time.sleep(0.002)
        time.sleep(0.001)
    outer, inner = _last("test.outer"), _last("test.inner")
    assert inner.parent == "test.outer"
    assert outer.child_ns == inner.end_ns - inner.start_ns
    assert outer.self_seconds == pytest.approx(outer.seconds - inner.seconds)
    assert inner.seconds >= 0.002 and outer.self_seconds >= 0.001
    after = spans.totals()["test.outer"]
    assert after.count == before.count + 1
    assert after.total_s - before.total_s == pytest.approx(outer.seconds)
    assert after.self_s - before.self_s == pytest.approx(outer.self_seconds)


def test_a_span_left_by_an_exception_is_closed_and_kept():
    with pytest.raises(ValueError):
        with spans.span("test.raises"):
            raise ValueError("boom")
    assert _last("test.raises").end_ns > 0
    with spans.span("test.after"):
        pass
    assert _last("test.after").parent is None


def test_the_ring_keeps_only_the_newest_spans():
    for i in range(spans.RING_SIZE + 10):
        with spans.span(f"test.ring.{i}"):
            pass
    names = [r.name for r in spans.records()]
    assert len(names) == spans.RING_SIZE
    assert names[-1] == f"test.ring.{spans.RING_SIZE + 9}"
    assert "test.ring.9" not in names and "test.ring.10" in names


def test_counters_are_filed_under_the_innermost_span():
    total = spans.counters()["test.count"]
    with spans.span("test.counting"):
        spans.count("test.count", 2)
        with spans.span("test.counting.child"):
            spans.count("test.count")
    assert _last("test.counting").counters == {"test.count": 2}
    assert _last("test.counting.child").counters == {"test.count": 1}
    assert spans.counters()["test.count"] == total + 3


def test_a_jit_in_a_span_files_one_trace_and_a_second_call_none():
    x = jnp.arange(4.0)
    # lax primitives only: a jnp function is a jit of its own, and would
    # be traced as one more program inside this one.
    f = jax.jit(lambda v: jax.lax.add(jax.lax.mul(v, v), v))
    with spans.span("test.first_call"):
        jax.block_until_ready(f(x))
    with spans.span("test.second_call"):
        jax.block_until_ready(f(x))
    first = _last("test.first_call").counters
    assert first["jit.traces"] == 1
    assert first["jit.trace_s"] > 0
    assert first["jit.lower_s"] > 0 and first["jit.compile_s"] > 0
    assert "jit.traces" not in _last("test.second_call").counters


def test_spans_join_a_profiler_recording(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("test.profiled"):
            jax.block_until_ready(jnp.arange(1000.0).sum())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "repro.test.profiled" in names


HLO = """HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/sched/mul"}
}

ENTRY %main.5 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  ROOT %fusion = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/handlers/pc.CS/mul"}
}
"""


def test_hlo_op_scopes_keep_ops_outside_fused_computations():
    assert spans.hlo_op_scopes(HLO) == {
        "Arg_0.1": "", "fusion": "jit(f)/handlers/pc.CS/mul"}


@pytest.fixture(scope="module")
def rw16():
    """A P=16 rma_rw Session after one run_batch, and the op scopes of
    its program, taken after the Session is gone."""
    spec = LockSpec.paper_default("rma_rw", 16, writer_fraction=0.25)
    builds = spans.counters()["program.builds"]
    sess = Session(spec, target_acq=2)
    built = spans.counters()["program.builds"] - builds
    pruned = engine.unreachable_pcs(sess.program, sess.env, faults=False)
    jax.block_until_ready(sess.run_batch(np.arange(3)))
    records = spans.records()
    traced = _traced_scopes(jax.make_jaxpr(lambda st: engine.step_loop(
        sess.table, sess.max_events, st, 0))(sess.state0).jaxpr)
    del sess
    gc.collect()
    return {"records": records, "scopes": spans.op_scopes(),
            "traced": traced, "pruned": pruned, "built": built}


def _traced_scopes(jaxpr) -> set:
    """The name stacks of every equation of `jaxpr`, nested ones too."""
    out = set()
    for eqn in jaxpr.eqns:
        out.add(str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out |= _traced_scopes(sub)
    return out


def test_session_spans_nest_as_documented(rw16):
    parents = {}
    for r in rw16["records"]:
        if r.name.startswith("session."):
            parents[r.name] = r.parent
    assert parents["session.build"] is None
    for child in ("session.layout", "session.handlers", "session.init_state"):
        assert parents[child] == "session.build"
    assert parents["session.run_batch"] is None
    for child in ("session.seeds_to_device", "session.dispatch"):
        assert parents[child] == "session.run_batch"
    handlers = [r for r in rw16["records"] if r.name == "session.handlers"]
    assert handlers[-1].counters.get("program.builds") == 1
    assert rw16["built"] == 1


def test_every_handler_op_carries_its_pc_scope(rw16):
    scopes = rw16["scopes"]
    in_handlers = [s for s in scopes.values() if "/handlers/" in s]
    assert in_handlers and any("/sched/" in s for s in scopes.values())
    pcs = set()
    for s in in_handlers:
        m = re.search(r"/handlers/(?:.*/)?pc\.(\w+)/", s)
        if m:
            pcs.add(m.group(1))
        else:
            # The switch's own work: clamping its index and, under vmap,
            # selecting among the handlers' results.
            assert s.rsplit("/", 1)[1] in ("clamp", "select_n"), s
    live = {hier.PC_NAMES[pc] for pc in range(hier.N_PCS)
            if pc not in rw16["pruned"]}
    # The crash-free program holds the live handlers and no other. The
    # compiler may fold all of a small handler's ops into ops of other
    # scopes (the select among the results, another handler's equal
    # ops), so the traced loop shows every live handler.
    traced = {m.group(1) for s in rw16["traced"]
              for m in [re.search(r"handlers/(?:.*/)?pc\.(\w+)", s)] if m}
    assert traced == live
    assert pcs <= live


def test_scoped_handlers_keep_their_module_and_name():
    spec = LockSpec.paper_default("rma_rw", 16, writer_fraction=0.25)
    sess = Session(spec, target_acq=2)
    for h in sess.handlers:
        assert h.__module__ == hier.__name__
    assert sess.handlers[hier.CS].__name__ == "cs_instr"

"""The trace reduction and the peak table, on traces built by hand and
on one recorded here."""
import pytest

from bench import peaks, trace
from bench.trace import DeviceOps, summarize


def test_union_merges_overlaps_and_touching_intervals():
    got = trace.union_ns([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)])
    assert got == [[0, 4], [5, 7], [9, 10]]


def test_busy_idle_and_op_time_inside_the_window():
    dev = DeviceOps("/device:TPU:0", [
        (100, 200, "fusion.1"), (150, 250, "fusion.2"),   # overlap
        (400, 500, "dht_lookup.1"), (900, 1100, "fusion.1")])
    spans = [(0, 1000, "bench.window"), (250, 400, "bench.dht.wait"),
             (500, 900, "bench.dht.next_batch"), (0, 1000, "bench.sim.call")]
    s = summarize([dev], spans)
    assert s.window == (0, 1000)
    # busy: [100, 250] + [400, 500] + [900, 1000] (clipped) = 350 ns
    assert s.busy_s() == pytest.approx(350e-9)
    assert s.idle_share() == pytest.approx(1 - 0.35)
    ops = s.op_seconds()
    assert ops["fusion.1"] == pytest.approx(200e-9)   # 100 + 100 clipped
    assert ops["fusion.2"] == pytest.approx(100e-9)
    kernel = s.op_seconds(lambda n: n.startswith("dht_"))
    assert list(kernel) == ["dht_lookup.1"]


def test_gaps_are_labelled_by_the_innermost_covering_host_span():
    dev = DeviceOps("/device:TPU:0", [(100, 200, "a"), (400, 500, "b")])
    spans = [(0, 1000, "bench.window"), (0, 1000, "bench.sim.call"),
             (200, 400, "bench.dht.wait"), (500, 1000, "bench.dht.next_batch")]
    s = summarize([dev], spans)
    assert s.gaps() == [(0, 100), (200, 400), (500, 1000)]
    b = s.breakdown()
    labels = [g[0] for g in b["idle_gaps"]]
    assert labels[0] == "bench.dht.next_batch"           # the longest gap
    assert b["idle_gaps"][0][1] == pytest.approx(500e-9)
    assert "bench.dht.wait" in labels
    assert s.host_label(0, 100) == "bench.sim.call"
    assert b["device_ops"][0][0] in ("a", "b")


def test_busy_and_idle_are_means_over_devices():
    d0 = DeviceOps("/device:TPU:0", [(0, 500, "x")])
    d1 = DeviceOps("/device:TPU:1", [(0, 100, "x")])
    s = summarize([d0, d1], [(0, 1000, "bench.window")])
    assert s.busy_s_by_device() == pytest.approx([500e-9, 100e-9])
    assert s.idle_share() == pytest.approx(1 - 0.3)
    assert s.op_seconds()["x"] == pytest.approx(300e-9)


def test_without_a_window_span_the_window_is_the_ops_extent():
    s = summarize([DeviceOps("/device:TPU:0", [(10, 20, "x"),
                                               (30, 50, "y")])], [])
    assert s.window == (10, 50)
    assert s.idle_share() == pytest.approx(0.25)


def test_a_recorded_trace_yields_the_bench_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    tracer = trace.Tracer()
    with tracer.window(True):
        with tracer.span("test.work"):
            jax.block_until_ready(jnp.arange(1000.0).sum())
    s = tracer.read()
    assert s is not None
    names = {n for _, _, n in s.spans}
    assert {"bench.window", "bench.test.work"} <= names
    assert s.window_s > 0


def test_op_names_are_cut_from_the_hlo_text():
    assert trace.op_name("%fusion.5 = s32[4096]{0} fusion(%a), kind=kLoop") \
        == "fusion.5"
    assert trace.op_name("%dht_lookup.1 = (s32[16384,1,512]) custom-call("
                         "%b), custom_call_target=\"tpu_custom_call\"") \
        == "dht_lookup.1"
    assert trace.op_name("while.3") == "while.3"


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")

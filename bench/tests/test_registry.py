"""The harness finds configurations, mixes and metrics by name, and
`BENCHMARK.json` keeps to the shape the harness reads."""
import json
import os
import re
import shutil

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


DUMMY_GENERATOR = """
def calls(mix, seed):
    while True:
        yield [seed] * mix["per_call"]


def submit(session, call):
    return session(call)


def lane_seeds(call):
    return list(call)
"""


def test_a_new_config_mix_and_metric_are_found_from_files_alone(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(harness.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = harness.load_benchmark()
    (bench_dir / "configs" / "dummy_lock.json").write_text(json.dumps(
        {"system": "lock_sim", "lock": {"P": 16}}))
    (bench_dir / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"generator": "dummy_gen", "per_call": 2}))
    (bench_dir / "generators" / "dummy_gen.py").write_text(DUMMY_GENERATOR)
    (bench_dir / "layer_metrics" / "dummy.metric.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx['trace'] else None\n")
    bench["configs"].append({"name": "dummy_lock", "source": "s",
                             "file": "bench/configs/dummy_lock.json",
                             "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy_lock",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "w"})
    bench["per_layer"].append({"name": "dummy.metric", "unit": "x",
                               "better": "higher", "source": "device_trace",
                               "layer": "dummy", "moves": "sim_runs_per_s",
                               "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    assert "dummy_lock" in harness.list_configs(str(bench_dir))
    assert "dummy_mix" in harness.list_mixes(str(bench_dir))
    assert "dummy_gen" in harness.list_generators(str(bench_dir))
    assert "dummy.metric" in harness.list_layer_metrics(str(bench_dir))
    loaded = harness.load_benchmark(str(tmp_path))
    cell = harness.Cell.from_benchmark("dummy.cell", loaded,
                                       bench_dir=str(bench_dir), seed=3)
    assert cell.cfg["lock"]["P"] == 16 and cell.mix["per_call"] == 2
    assert cell.chips == 1 and cell.seed == 3
    gen = harness.traffic_generator(cell.mix, cell.bench_dir)
    call = next(gen.calls(cell.mix, cell.seed))
    assert gen.submit(len, call) == 2 and gen.lane_seeds(call) == [3, 3]
    got = harness.read_layer_metrics(loaded, "dummy.cell", {"trace": 1},
                                     str(bench_dir))
    assert got == {"dummy.metric": {"value": 42.0, "unit": "x"}}
    assert harness.read_layer_metrics(loaded, "dummy.cell", {"trace": 0},
                                      str(bench_dir)) == {}
    with pytest.raises(KeyError):
        harness.Cell.from_benchmark("no.such.cell", loaded,
                                    bench_dir=str(bench_dir))


def test_benchmark_json_names_files_that_exist_and_metrics_that_fit():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
        assert c["file"].startswith("bench/")
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert w["traffic"] in harness.list_mixes()
        mix = harness.load_json(harness.BENCH_DIR, "traffic", w["traffic"])
        assert mix["generator"] in harness.list_generators()
        with open(os.path.join(harness.ROOT, configs[w["config"]]["file"])) as f:
            cfg = json.load(f)
        assert os.path.isfile(os.path.join(
            harness.BENCH_DIR, "systems", cfg["system"] + ".py"))
        assert os.path.isfile(os.path.join(
            harness.BENCH_DIR, "reference", cfg["system"] + ".py"))
        reported = [m["name"] for m in harness.metrics_of(bench, w["name"],
                                                          "end_to_end")]
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.metrics_of(bench, w["name"], "per_layer")
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["name"] in harness.list_layer_metrics()
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert w in cells
            assert "workloads" not in moved or w in moved["workloads"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for name in list(configs) + list(cells) + [m["name"] for m in
                                               bench["end_to_end"]
                                               + bench["per_layer"]]:
        assert NAME.match(name), name

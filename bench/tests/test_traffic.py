"""Traffic generators: a seed repeats its traffic, seeds differ, and
every mix names a generator that exists."""
import json
import os

import numpy as np

from bench import harness

TRAFFIC = os.path.join(harness.BENCH_DIR, "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def take(gen, n):
    return [next(gen) for _ in range(n)]


def test_fresh_seeds_repeat_per_seed_and_never_repeat_within_a_run():
    m = mix("seeds8")
    gen = harness.traffic_generator(m)
    big = 2**31 + 12345
    a = take(gen.calls(m, big), 50)
    b = take(gen.calls(m, big), 50)
    c = take(gen.calls(m, big + 1), 50)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(np.concatenate(a), np.concatenate(c))
    flat = np.concatenate(a)
    assert flat.dtype == np.int32 and flat.min() >= 0
    assert np.unique(flat).size == flat.size
    assert all(x.size == m["seeds_per_call"] for x in a)
    assert take(gen.calls(dict(m, seeds_per_call=2), -3), 1)[0].size == 2
    assert gen.lane_seeds(a[0]) == a[0].tolist()


def test_every_mix_file_names_a_known_loop():
    for name in os.listdir(TRAFFIC):
        assert mix(name[:-5])["generator"] in harness.list_generators()

"""The benchmark's traffic generator (bench/traffic.py)."""
import json
import os

import numpy as np
import pytest

from bench import traffic

MIXES = os.path.join(os.path.dirname(__file__), "..", "..", "bench", "mixes")
DATA = os.path.join(os.path.dirname(__file__), "data")


def _mix(name):
    """A mix of the benchmark's, or a test mix of data/ (poisson-mix: an
    open-loop Poisson mix that no cell uses yet)."""
    path = os.path.join(MIXES, name + ".json")
    if not os.path.exists(path):
        path = os.path.join(DATA, name + ".json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["batch-decode", "poisson-mix"])
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.generate(mix, 2**31 + 12345, 20, 92416)
    b = traffic.generate(mix, 2**31 + 12345, 20, 92416)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.rid, x.max_new, x.arrival) == (y.rid, y.max_new, y.arrival)
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["batch-decode", "poisson-mix"])
def test_seeds_share_the_work(name):
    """Two seeds: the same multisets of lengths and gaps, other tokens and
    another order."""
    mix = _mix(name)
    a = traffic.generate(mix, 7, 20, 92416)
    b = traffic.generate(mix, 2**33 + 7, 20, 92416)
    key = lambda rs: sorted(len(r.prompt) for r in rs)
    assert key(a) == key(b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert abs(max(r.arrival for r in a) - max(r.arrival for r in b)) < 1e-9
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("name", ["batch-decode", "poisson-mix"])
def test_lengths_clipped_and_chunk_quantized(name):
    mix = _mix(name)
    rs = traffic.generate(mix, 99, 30, 1000)
    p = np.array([len(r.prompt) for r in rs])
    o = np.array([r.max_new for r in rs])
    assert p.min() >= mix["prompt"]["min"] and p.max() <= mix["prompt"]["max"]
    assert o.min() >= mix["output"]["min"] and o.max() <= mix["output"]["max"]
    assert np.all(p % mix["chunk"] == 0)
    assert traffic.chunk_lengths(rs, mix["chunk"]) == [mix["chunk"]]
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
               for r in rs)
    assert (p + o).max() <= mix["max_len"]


def test_work_scales_with_seconds():
    mix = _mix("batch-decode")
    per_s = mix["arrivals"]["requests_per_s"]
    assert len(traffic.generate(mix, 1, 10, 50)) == round(per_s * 10)
    assert len(traffic.generate(mix, 1, 40, 50)) == round(per_s * 40)
    on = _mix("poisson-mix")
    rs = traffic.generate(on, 1, 40, 50)
    assert rs[0].arrival == 0.0
    span = max(r.arrival for r in rs)
    assert 0.8 * 40 < span < 1.2 * 40


def test_backlog_longest_outputs_first():
    mix = _mix("batch-decode")
    rs = traffic.generate(mix, 5, 20, 50)
    assert all(r.arrival == 0.0 for r in rs)
    s = mix["slots"]
    groups = [[r.max_new for r in rs[i:i + s]] for i in range(0, len(rs), s)]
    for g0, g1 in zip(groups, groups[1:]):
        assert min(g0) >= max(g1)


def test_lognormal_median_and_clip():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64,
            "max": 512}
    x = traffic.lengths(spec, 1001)
    assert x[500] == 256
    assert x.min() == 64 or x.min() > 64
    assert (x == 512).mean() == pytest.approx(0.124, abs=0.01)


def test_negative_seed_refused():
    with pytest.raises(ValueError):
        traffic.seed_words(-1, 2)


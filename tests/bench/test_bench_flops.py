"""Operations and bytes the benchmark counts (bench/flops.py and each
family's `dispatches`, `row_flops` and `request_flops`)."""
import json
import os

import pytest

from bench import families, flops

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                       "configs")
PINS = os.path.join(os.path.dirname(__file__), "data", "parent-pins.json")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_wq_call_by_hand():
    """One packed call of codeqwen's wq (4096 x 4096) on a 32-slot pool:
    2 m k n operations; bytes = f32 conductance differences, a normalizer
    and an ADC step per (128-row tile, column), f32 inputs and outputs."""
    ops, moved = flops.cim_call(32, 4096, 4096, 128)
    assert ops == 2 * 32 * 4096 * 4096 == 1_073_741_824
    assert moved == 4 * (4096 * 4096 + 2 * 32 * 4096 + 32 * 4096
                         + 32 * 4096) == 69_206_016


def test_calls_of_a_window():
    c = _config("codeqwen15-7b-L2")
    calls = flops.cim_calls(c, decode_steps=10, slots=32, chunk_rows=[32])
    # decode group + one chunk, 7 projections x 2 layers a step
    assert len(calls) == 2 * 7 * 2
    wq = flops.cim_call(32, 4096, 4096, 128)
    assert calls[0] == (wq[0] * 10, wq[1] * 10)
    assert calls[7] == calls[0]                 # layer 1's wq
    assert calls[14] == wq


@pytest.mark.parametrize("name", ["codeqwen15-7b-L2", "rwkv6-7b-L2"])
def test_request_flops_matches_row_sum(name):
    """The closed form over prompt rows equals the row-by-row sum."""
    c = _config(name)
    fam = families.family(c)
    p, n = 96, 7
    rows = sum(fam.row_flops(c, kv, False) for kv in range(1, p + 1))
    rows += 2.0 * c["hidden_size"] * c["vocab_size"]
    rows += sum(fam.row_flops(c, p + j, True) for j in range(1, n))
    assert fam.request_flops(c, p, n) == pytest.approx(rows, rel=1e-12)


def test_codeqwen_row_by_hand():
    c = _config("codeqwen15-7b-L2")
    d, f, v, kv = 4096, 13440, 92416, 4 * 128       # GQA: 4 KV heads
    params = 2 * d * d + 2 * d * kv + 3 * d * f
    want = 2 * (2 * params + 4 * d * 100) + 2 * d * v
    assert families.family(c).row_flops(c, 100, True) == want


def test_rwkv6_projection_parameters():
    c = _config("rwkv6-7b-L2")
    calls = families.family(c).dispatches(c)
    assert len(calls) == 8 * 2
    n = sum(k * m for k, m in calls) // c["num_hidden_layers"]
    assert n == 6 * 4096 * 4096 + 2 * 4096 * 14336      # 218 M a layer


@pytest.mark.parametrize("name", ["codeqwen15-7b-L2", "rwkv6-7b-L2"])
def test_numbers_equal_the_parents(name):
    """Every number the two published configurations' cells read from
    bench/flops and their family files equals, exactly, what the code
    before the family files gave (data/parent-pins.json): a window's
    operations, bytes and least time at the v5e's peaks (one entry per
    call now, where that code summed a projection's layers in one), the
    kernel events a step holds, row and request FLOPs. Integers below
    2**53 as floats, so any order of summation is exact."""
    with open(PINS) as f:
        pins = json.load(f)
    want = pins["configs"][name]
    win = pins["window"]
    c = _config(name)
    fam = families.family(c)
    chunks = [m for m, n in win["chunk_rows"] for _ in range(n)]
    calls = flops.cim_calls(c, win["decode_steps"], win["slots"], chunks)
    per_step = len(fam.dispatches(c))
    assert per_step == want["kernel_events_per_step"]
    assert len(calls) == (1 + len(chunks)) * per_step
    assert sum(o for o, _ in calls) == want["calls_ops"]
    assert sum(b for _, b in calls) == want["calls_bytes"]
    peaks = win["peaks"]
    least = sum(max(o / peaks["flops_per_s"], b / peaks["hbm_bytes_per_s"])
                for o, b in calls)
    assert least == want["least_s"]
    assert [fam.row_flops(c, kv, head) for kv, head in
            pins["row_flops_at"]] == want["row_flops"]
    assert [fam.request_flops(c, p, n) for p, n in
            pins["request_flops_at"]] == want["request_flops"]

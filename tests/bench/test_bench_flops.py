"""Operations and bytes the benchmark counts (bench/flops.py)."""
import json
import os

import pytest

from bench import flops

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                       "configs")


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
    assert len(calls) == 2 * 7                 # decode group + one chunk
    wq = flops.cim_call(32, 4096, 4096, 128)
    assert calls[0] == (wq[0] * 10 * 2, wq[1] * 10 * 2)
    assert calls[7] == (wq[0] * 2, wq[1] * 2)


@pytest.mark.parametrize("name", ["codeqwen15-7b-L2", "rwkv6-7b-L2"])
def test_request_flops_matches_row_sum(name):
    """The closed form over prompt rows equals the row-by-row sum."""
    c = _config(name)
    p, n = 96, 7
    rows = sum(flops.row_flops(c, kv, False) for kv in range(1, p + 1))
    rows += 2.0 * c["hidden_size"] * c["vocab_size"]
    rows += sum(flops.row_flops(c, p + j, True) for j in range(1, n))
    assert flops.request_flops(c, p, n) == pytest.approx(rows, rel=1e-12)


def test_codeqwen_row_by_hand():
    c = _config("codeqwen15-7b-L2")
    d, f, v, kv = 4096, 13440, 92416, 4 * 128       # GQA: 4 KV heads
    params = 2 * d * d + 2 * d * kv + 3 * d * f
    want = 2 * (2 * params + 4 * d * 100) + 2 * d * v
    assert flops.row_flops(c, 100, True) == want


def test_rwkv6_projection_parameters():
    c = _config("rwkv6-7b-L2")
    n = sum(k * m for k, m in flops.projections(c).values())
    assert n == 6 * 4096 * 4096 + 2 * 4096 * 14336      # 218 M a layer

"""Operations and bytes, computed from shapes in the configuration file.

Model FLOPs (each family's `row_flops` and `request_flops`, in
bench/families/) follow the convention of `repro.launch.roofline._n_eff`
(copied so that the program cannot move the yardstick): 2 x matmul
parameters per token row, embeddings as lookups, the LM head counted where
its logits are used, plus what the family's mixing costs per row (attention
over the row's actual KV length, a recurrence). Rows of empty pool slots do
no useful work and are not counted.

A packed CIM call's bytes are what the algorithm must move: the K x N
conductance differences at the width the chip model defines (float32
today), the per-core normalizer and ADC step of each (row tile, column)
pair, and float32 inputs and outputs. Padding tiles, grid order and
re-reads are not counted, so the roofline reads the same work whatever
implements the kernel.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple

from .families import family

F32 = 4


def cim_call(m: int, k: int, n: int, core_rows: int) -> Tuple[float, float]:
    """(operations, bytes) of one packed CIM call on m rows."""
    row_tiles = -(-k // core_rows)
    ops = 2.0 * m * k * n
    moved = F32 * (k * n + 2 * row_tiles * n + m * k + m * n)
    return ops, moved


def cim_calls(c: dict, decode_steps: int, slots: int,
              chunk_rows: Iterable[int]) -> List[Tuple[float, float]]:
    """(ops, bytes) of every packed CIM call of a window, one entry per
    call of a step (the family's `dispatches`) and kind of step: each
    decode step makes every call on all `slots` rows, each prefill chunk
    on its own rows."""
    rows = c["cim"]["core_rows"] // (2 if c["cim"]["differential_rows"]
                                     else 1)
    calls = family(c).dispatches(c)
    out = []
    for m, times in [(slots, decode_steps)] + [(m, 1) for m in chunk_rows]:
        for k, n in calls:
            ops, moved = cim_call(m, k, n, rows)
            out.append((ops * times, moved * times))
    return out

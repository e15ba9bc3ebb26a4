"""Operations and bytes, computed from shapes in the configuration file.

Model FLOPs follow the convention of `repro.launch.roofline._n_eff` (copied
here so that the program cannot move the yardstick): 2 x matmul parameters
per token row, embeddings as lookups, the LM head counted where its logits
are used, plus attention over each row's actual KV length (4 d kv_len per
layer: scores and the weighted sum), or the RWKV-6 recurrence (4 d N per
layer). Rows of empty pool slots do no useful work and are not counted.

A packed CIM call's bytes are what the algorithm must move: the K x N
conductance differences at the width the chip model defines (float32
today), the per-core normalizer and ADC step of each (row tile, column)
pair, and float32 inputs and outputs. Padding tiles, grid order and
re-reads are not counted, so the roofline reads the same work whatever
implements the kernel.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

F32 = 4


def projections(c: dict) -> Dict[str, Tuple[int, int]]:
    """(K, N) of each CIM projection of one layer."""
    d, f = c["hidden_size"], c["intermediate_size"]
    if c["family"] == "transformer":
        q = c["num_attention_heads"] * c["head_dim"]
        kv = c["num_key_value_heads"] * c["head_dim"]
        return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
                "w_g": (d, f), "w_i": (d, f), "w_o": (f, d)}
    if c["family"] == "rwkv6":
        return {"wr": (d, d), "wk": (d, d), "wv": (d, d), "wg": (d, d),
                "wo": (d, d), "ck": (d, f), "cv": (f, d), "cr": (d, d)}
    raise ValueError(f"unknown family {c['family']!r}")


def cim_call(m: int, k: int, n: int, core_rows: int) -> Tuple[float, float]:
    """(operations, bytes) of one packed CIM call on m rows."""
    row_tiles = -(-k // core_rows)
    ops = 2.0 * m * k * n
    moved = F32 * (k * n + 2 * row_tiles * n + m * k + m * n)
    return ops, moved


def cim_calls(c: dict, decode_steps: int, slots: int,
              chunk_rows: Iterable[int]) -> List[Tuple[float, float]]:
    """(ops, bytes) of every packed CIM call of a window: each decode step
    runs every projection of every layer on all `slots` rows, each prefill
    chunk on its own rows."""
    rows = c["cim"]["core_rows"] // (2 if c["cim"]["differential_rows"]
                                     else 1)
    per_layer = projections(c).values()
    layers = c["num_hidden_layers"]
    out = []
    shapes = [(slots, decode_steps)] + [(m, 1) for m in chunk_rows]
    for m, times in shapes:
        for k, n in per_layer:
            ops, moved = cim_call(m, k, n, rows)
            out.append((ops * times * layers, moved * times * layers))
    return out


def row_flops(c: dict, kv_len: int, head: bool) -> float:
    """Model FLOPs of one useful token row at KV length kv_len (the row
    itself included), with or without the LM head."""
    d = c["hidden_size"]
    per_layer = 2.0 * sum(k * n for k, n in projections(c).values())
    if c["family"] == "transformer":
        per_layer += 4.0 * c["num_attention_heads"] * c["head_dim"] * kv_len
    else:
        r = c["decay_lora_rank"]
        per_layer += 2.0 * 2 * d * r + 4.0 * d * c["head_size"]
    flops = per_layer * c["num_hidden_layers"]
    if head:
        flops += 2.0 * d * c["vocab_size"]
    return flops


def request_flops(c: dict, prompt_len: int, n_tokens: int) -> float:
    """Model FLOPs a request needs: every prompt row (the LM head only on
    the last, which yields the first token), then one decode row per
    further token."""
    total = 0.0
    if c["family"] == "transformer":
        # sum over prompt rows of kv_len = 1..P, done in closed form
        no_head = row_flops(c, 0, False)
        att = 4.0 * c["num_attention_heads"] * c["head_dim"] \
            * c["num_hidden_layers"]
        total += prompt_len * no_head + att * prompt_len * (prompt_len + 1) / 2
    else:
        total += prompt_len * row_flops(c, 0, False)
    total += 2.0 * c["hidden_size"] * c["vocab_size"]
    for j in range(1, n_tokens):
        total += row_flops(c, prompt_len + j, True)
    return total

"""Jit'd public wrappers around the CIM MVM Pallas kernels.

`cim_mvm` is the single-matrix fast path used by models in chip-sim mode. It
consumes the *folded* representation (differential conductance gd = g_pos -
g_neg and the per-column normalizer) and returns signed ADC counts.

`cim_mvm_packed` executes a whole layer's TNSA tile plan
(core/mapping.PackedPlan) in one compiled dispatch — the serving path used
by core.cim.CIMEngine. Row-split partial sums are accumulated digitally
inside the kernel; per-tile counts are weighted by the plan's denorm_tiles
(valid-column mask, optionally with norm * v_decr folded in). Plans whose
schedule has more than one pass (merged cores time-shared via seq_slot)
route to the pass-major scheduled kernel; single-pass plans keep the PR-1
tile-grid kernel, so unmerged plans pay no scheduling cost.
Transpose-direction plans (core/mapping.pack_tiles_transposed — the BL->SL
read of the same programmed tile stack) route to the transpose-direction
kernel regardless of pass structure.

The scheduled and transpose-direction kernels consume the plan's FUSED run
layout (out_slot/out_col, computed at pack time): output runs accumulate
in-kernel and only blocks genuinely revisited across passes fall back to a
small post-dispatch fold. `fused=False` forces the per-slot-partial layout
(one partial block per slot, whole reduction after the dispatch) — the
pre-fusion baseline, kept for benchmarking the win and for parity tests.

A plan either owns its tiles or reads them in place from a whole layer
stack at its `stack_index` (core/mapping.split_tile_stacks): the kernels
take both as a stack and a position (`_tile_stack`), the constant 0 for a
plan owning its tiles.

The batch block shape defaults to the autotuner's cached winner for the
plan's signature (`autotune.lookup`; 256 until `autotune.tune` has measured
the shape) — pass bm explicitly to pin it.

interpret=None picks the mode from the backend (`default_interpret`):
interpreted on CPU, compiled on TPU, an error anywhere else.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from . import autotune
from .kernel import (cim_mvm_pallas, cim_mvm_packed_pallas,
                     cim_mvm_scheduled_pallas, cim_mvm_transposed_pallas)

if TYPE_CHECKING:      # see ref.py: no core import while kernels load
    from ...core.types import CIMConfig


# The packed kernels, by the name their jit carries into a jaxpr, and the
# place of `stack_index` among their array arguments (launch/scheduler.
# packed_dispatches reads both).
PACKED_KERNELS = ("cim_mvm_packed_pallas", "cim_mvm_scheduled_pallas",
                  "cim_mvm_transposed_pallas")
STACK_INDEX_ARG = 6


def _tile_stack(packed):
    """(gd (S, T, bk, bn), position) of a plan's tiles. A plan owning its
    tiles is a stack of one at position 0; a stack-indexed plan passes its
    whole stack, leading dims merged (a reshape that moves no bytes)."""
    gd = packed.gd_tiles
    if packed.stack_index is None:
        if gd.ndim != 3:
            raise ValueError(
                f"plan '{packed.layer}' has tiles of shape {gd.shape}: "
                "index one layer (and shard) first, or split its stack "
                "(core/mapping.split_tile_stacks)")
        return gd[None], 0
    if jnp.ndim(packed.stack_index) != 0:
        raise ValueError(
            f"plan '{packed.layer}' still spans stack positions of shape "
            f"{jnp.shape(packed.stack_index)}: take one layer and shard "
            "first (core/mapping.take)")
    return gd.reshape((-1,) + gd.shape[-3:]), packed.stack_index


def default_interpret() -> bool:
    """Interpret mode on the CPU backend, compiled Mosaic kernels on TPU.
    Any other backend raises: silently interpreting there would report
    interpreter numbers as device results."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; the "
        f"{backend!r} backend is neither (set JAX_PLATFORMS=cpu to test)")


def cim_mvm(x_int, g_pos, g_neg, v_decr, cfg: CIMConfig, *, seed=0,
            norm=None, block=(256, 256, 256), interpret=None):
    """CIM MVM returning signed ADC counts, shape (B, C) float32.

    x_int: (B, R) integer-valued float or int array.
    g_pos/g_neg: (R, C) conductances in uS.
    """
    if interpret is None:
        interpret = default_interpret()
    gd = (g_pos - g_neg).astype(jnp.float32)
    if norm is None:
        norm = jnp.sum(g_pos + g_neg, axis=0)
    inv_norm = 1.0 / norm.astype(jnp.float32)
    bm, bk, bn = block
    return cim_mvm_pallas(
        x_int.astype(jnp.float32), gd, inv_norm,
        jnp.asarray(v_decr, jnp.float32), jnp.asarray(seed, jnp.int32),
        activation=cfg.activation, n_max=cfg.out_mag_levels,
        v_read=cfg.v_read, bm=bm, bk=bk, bn=bn, interpret=interpret)


def packed_call(x, packed, *, activation: str, n_max: int, v_read: float,
                seed=0, bm=None, interpret=None, scheduled=None,
                fused: bool = True):
    """Single entry point to the packed kernels: validates the plan/input
    fit, runs ONE pallas_call over every tile, slices the padding off.
    All packed executors (CIM and raw-matmul) funnel through here so the
    padding and error contracts cannot drift apart.

    scheduled: None routes by the plan (pass-major scheduled kernel iff
    n_passes > 1); True/False forces a kernel (benchmark use — a scheduled
    plan can always run the scheduled kernel, but multi-pass plans cannot
    run the tile-grid one).
    fused: False degrades the scheduled / transpose-direction kernels to
    the per-slot-partial layout (out_slot identity, one partial block per
    slot, full post-dispatch reduction) — the pre-fusion baseline for
    benchmarks and bitwise-parity tests. The grid order is unchanged, only
    the reduction grouping moves, so both settings agree bitwise on
    integer-valued counts.
    bm: batch block rows; None takes the autotuner's cached winner for this
    plan signature (`autotune.lookup`, default 256 before any `tune`).
    """
    if x.shape[-1] != packed.n_rows:
        raise ValueError(
            f"input has {x.shape[-1]} features but plan "
            f"'{packed.layer}' covers {packed.n_rows} weight rows")
    if interpret is None:
        interpret = default_interpret()
    if bm is None:
        bm = autotune.lookup(packed, x.shape[0], activation)
    gd, pos = _tile_stack(packed)
    n_slots = packed.n_tiles
    out_slot = packed.out_slot if fused else tuple(range(n_slots))
    out_col = packed.out_col if fused else packed.col_block
    if packed.transpose:
        # transpose-direction plan: one kernel serves any pass structure
        # (runs never straddle a pass's block re-sort — `scheduled` is moot)
        out = cim_mvm_transposed_pallas(
            x.astype(jnp.float32), gd, packed.inv_norm_tiles,
            packed.denorm_tiles, packed.v_decr_tiles,
            jnp.asarray(seed, jnp.int32), pos,
            in_block=packed.row_block, tile_slot=packed.tile_slot,
            out_slot=out_slot, out_col=out_col,
            activation=activation, n_max=n_max, v_read=v_read, bm=bm,
            interpret=interpret)
        return out[:x.shape[0], :packed.n_cols]
    if scheduled is None:
        scheduled = packed.n_passes > 1
    if packed.n_passes > 1 and not scheduled:
        raise ValueError(
            f"plan '{packed.layer}' has {packed.n_passes} sequential passes; "
            "the tile-grid kernel cannot serialize merged cores")
    if scheduled:
        out = cim_mvm_scheduled_pallas(
            x.astype(jnp.float32), gd, packed.inv_norm_tiles,
            packed.denorm_tiles, packed.v_decr_tiles,
            jnp.asarray(seed, jnp.int32), pos,
            row_block=packed.row_block, out_slot=out_slot,
            out_col=out_col, n_passes=packed.n_passes,
            activation=activation, n_max=n_max, v_read=v_read, bm=bm,
            interpret=interpret)
    else:
        out = cim_mvm_packed_pallas(
            x.astype(jnp.float32), gd, packed.inv_norm_tiles,
            packed.denorm_tiles, packed.v_decr_tiles,
            jnp.asarray(seed, jnp.int32), pos,
            row_block=packed.row_block, col_block=packed.col_block,
            activation=activation, n_max=n_max, v_read=v_read, bm=bm,
            interpret=interpret)
    return out[:x.shape[0], :packed.n_cols]


def cim_mvm_packed(x_int, packed, cfg: CIMConfig, *, seed=0, bm=None,
                   interpret=None, scheduled=None, fused: bool = True):
    """Packed whole-layer CIM MVM: one pallas_call for every tile of the
    plan, returning the digitally-accumulated (B, C) float32 output — summed
    ADC counts when the plan was packed with fold_norm=False (loop-executor
    semantics), or de-normalized charge units (counts * norm * v_decr summed
    over row splits) when packed with fold_norm=True (CIMEngine serving).

    x_int: (B, R) integer-valued activations covering the layer's full
    weight-row space; packed: core.mapping.PackedPlan. bm=None takes the
    autotuned block shape; fused=False forces the per-slot-partial baseline.
    """
    return packed_call(x_int, packed, activation=cfg.activation,
                       n_max=cfg.out_mag_levels, v_read=cfg.v_read,
                       seed=seed, bm=bm, interpret=interpret,
                       scheduled=scheduled, fused=fused)

"""Pallas TPU kernels: fused NeuRRAM CIM MVM (matmul + voltage-mode
normalization + ADC quantization + activation epilogue).

TPU adaptation (DESIGN.md section 2): the chip's motivation is avoiding data
movement; on TPU the analogous win is keeping the whole neuron datapath —
conductance-normalization, ADC charge-decrement quantization and the fused
activation — in VMEM/VREGs as an epilogue of the MXU matmul, so the analog
charge `q` never round-trips to HBM.

Two kernels share that epilogue:

  * `cim_mvm_pallas` — one (M, K) x (K, N) MVM on a single core's worth of
    conductances. Grid (i, j, k) iterates K innermost with a VMEM f32
    accumulator; the epilogue fires on the last K step.
  * `cim_mvm_packed_pallas` — a whole LAYER of the TNSA tile plan
    (core/mapping.PackedPlan) in one dispatch. The grid gains a leading
    tile dimension (i, t) over padded stacked tile tensors
    `gd_tiles (T, bk, bn)`; scalar-prefetched `row_block/col_block` index
    arrays steer each tile's input block and output block (grouped-matmul
    style), and row-split partial sums accumulate digitally INTO the output
    block: tiles are pre-sorted so all tiles of one output block are
    consecutive grid steps — the first zero-initializes the block, the rest
    add `counts * denorm`. This replaces the per-tile Python loop executor
    (one trace, one dispatch, batching-friendly) and serves single-pass
    (unmerged) plans.

A third kernel executes SCHEDULED plans (core/mapping.schedule_tiles):

  * `cim_mvm_scheduled_pallas` — pass-major grid (i, p, s): pass p runs the
    tiles the chip fires simultaneously (one per core), successive passes
    model the serialized access to merged cores (seq_slot > 0). Pallas TPU
    only preserves an output block's VMEM across CONSECUTIVE grid visits,
    so pack time re-sorts each pass's slots by output block
    (core/mapping._fused_layout) and hands the kernel a FUSED run layout
    (`out_slot`: slot -> run, `out_col`: run -> column block): every run of
    grid-consecutive same-block slots accumulates in-kernel exactly like
    the tile-grid kernel (first visit zero-initializes, the rest add), and
    one partial is emitted per RUN instead of per slot. Only a block
    genuinely revisited non-consecutively (a later pass's row split) spans
    several runs, and the wrapper folds just those after the dispatch —
    which is where the chip accumulates row-split partial sums too:
    digitally, outside the analog array. Idle padding slots carry zero
    denorm; their all-idle runs (out_col -1) are dropped by the wrapper.

A fourth kernel executes the TRANSPOSE direction (TNSA bidirectionality,
paper Fig. 4e-g — the BL->SL read of the same programmed cells):

  * `cim_mvm_transposed_pallas` — grid (i, t) over the SHARED forward tile
    stack (no transposed copy of the conductances): each slot contracts its
    stored (bk, bn) block on the COLUMN axis (x @ gd.T via dot_general),
    normalizes by the transpose direction's per-row normalizer and applies
    that direction's own calibrated ADC step. The transpose plan carries
    its OWN fused grid order (sorted by transpose-direction output block)
    while the conductance stack stays in forward order: a scalar-prefetched
    `tile_slot` map steers each grid step to its stored block, and the same
    run layout (`out_slot`/`out_col`) drives in-kernel accumulation with
    the per-run fallback fold in the wrapper, exactly like the scheduled
    kernel.

All three packed kernels read their tiles from a STACK `gd (S, T, bk, bn)`
at a scalar-prefetched position `stack_index`: a plan that owns its tiles
is a stack of one (S = 1, position 0); a layer of a scanned stack passes
the whole stack of every layer and shard, merged into S by a free reshape,
and its flat position in it (core/mapping.split_tile_stacks). The tile
BlockSpec's index map starts each DMA at (stack_index, tile); the kernel
bodies, grids and the bytes they read are the same either way, and the
layer's tiles are never copied out of the stack first.

The stochastic-activation (LFSR comparator-bit) path is supported in ALL
packed kernels: counts are neuron-unit bits, so the kernels weight them by
the valid-column mask (invn > 0) instead of the fold_norm denorm — one pack
serves both 'none' and 'stochastic' dispatches (the RBM Gibbs loop).

The bit-serial input loop of the chip is algebraically folded in all of
them (sum_k 2^k p_k = x_int, exact for the linear datapath); per-phase
non-ideality studies use the jnp oracle in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..prng import hash_uniform

# The analog charge is an f32 quantity: the TPU's default f32 matmul runs
# at reduced (bf16-pass) precision, which moved 55% of a codeqwen wq
# layer's summed ADC counts (by up to 6) against the f32 oracle on a v5e.
# HIGHEST keeps the contraction in f32 there; on the CPU interpreter it
# changes nothing.
_F32 = jax.lax.Precision.HIGHEST

# Trace counters (incremented while jit TRACES each wrapper, not per call):
# tests and benchmarks assert "one compiled dispatch per plan shape" with
# these. Keyed by kernel name.
TRACE_COUNTS = {"cim_mvm": 0, "cim_mvm_packed": 0, "cim_mvm_scheduled": 0,
                "cim_mvm_transposed": 0}


def _pwl_tanh(steps, n_max: float):
    """PWL tanh counter schedule — same math as ref.pwl_tanh_counts."""
    s = n_max / 47.0
    k0, k1, k2 = 35.0 * s, 40.0 * s, 43.0 * s
    st0 = k0
    st1 = k0 + 2.0 * (k1 - k0)
    st2 = st1 + 3.0 * (k2 - k1)
    out = jnp.where(
        steps <= st0, steps,
        jnp.where(steps <= st1, k0 + (steps - st0) * 0.5,
                  jnp.where(steps <= st2, k1 + (steps - st1) / 3.0,
                            k2 + (steps - st2) * 0.25)))
    return jnp.minimum(jnp.floor(out), n_max)


def _epilogue(q, vd, activation: str, n_max: int, seed_ref=None, ij=(0, 0)):
    if activation == "identity":
        return q                   # raw charge passthrough (exact matmul)
    sign = jnp.sign(q)
    # charge-decrement count: round-to-nearest (comparator flips mid-step)
    steps = jnp.floor(jnp.abs(q) / vd + 0.5)
    if activation == "relu":
        return jnp.minimum(steps, n_max) * (sign > 0)
    if activation in ("tanh", "sigmoid"):
        mag = _pwl_tanh(jnp.minimum(steps, 4.0 * n_max), float(n_max))
        out = sign * mag
        if activation == "sigmoid":
            out = jnp.floor((out + n_max) * 0.5)
        return out
    if activation == "stochastic":
        # LFSR-analogue: stateless hash PRNG, uniform in +-(vd * n_max).
        u = hash_uniform(q.shape, seed_ref[0], ij[0], ij[1]) * 2.0 - 1.0
        return (q + u * (vd * n_max) > 0).astype(jnp.float32)
    return sign * jnp.minimum(steps, n_max)


def _acc_weight(invn, den, activation: str):
    """Per-column digital accumulation weight for one tile's counts.

    Stochastic counts are comparator BITS in neuron units: the fold_norm
    serving pack's denorm (mask * norm * v_decr) is meaningless for them,
    so a stochastic dispatch masks valid columns instead (invn > 0 exactly
    on non-padded columns) — letting ONE pack serve both 'none'
    (de-normalized counts) and 'stochastic' (bit-sampling) dispatches of
    the same direction, as the RBM Gibbs loop does.
    """
    if activation == "stochastic":
        return (invn > 0).astype(jnp.float32)
    return den


def _cim_kernel(x_ref, gd_ref, invn_ref, vd_ref, seed_ref, out_ref, acc_ref, *,
                nk: int, v_read: float, activation: str, n_max: int):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], gd_ref[...], precision=_F32,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        q = acc_ref[...] * v_read * invn_ref[...]       # (BM,BN)*(1,BN)
        counts = _epilogue(q, vd_ref[0], activation, n_max, seed_ref,
                           ij=(i, j))
        out_ref[...] = counts.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "n_max", "v_read", "bm", "bk", "bn",
                     "interpret"))
def cim_mvm_pallas(x, gd, inv_norm, v_decr, seed, *, activation: str = "none",
                   n_max: int = 127, v_read: float = 0.5,
                   bm: int = 256, bk: int = 256, bn: int = 256,
                   interpret: bool = False):
    """x:(M,K) f32 integer-valued; gd:(K,N) f32; inv_norm:(N,) f32;
    v_decr: scalar f32; seed: scalar int32 (stochastic activation only).
    Returns (M,N) f32 ADC counts."""
    TRACE_COUNTS["cim_mvm"] += 1
    m, kdim = x.shape
    _, n = gd.shape
    bm, bk, bn = min(bm, m), min(bk, kdim), min(bn, n)

    def pad(a, mults):
        pads = [(0, -s % t) for s, t in zip(a.shape, mults)]
        return jnp.pad(a, pads) if any(p[1] for p in pads) else a

    xp = pad(x, (bm, bk))
    gdp = pad(gd, (bk, bn))
    invp = pad(inv_norm.reshape(1, -1), (1, bn))
    mp, kp = xp.shape
    np_ = gdp.shape[1]
    nk = kp // bk
    grid = (mp // bm, np_ // bn, nk)

    out = pl.pallas_call(
        functools.partial(_cim_kernel, nk=nk, v_read=v_read,
                          activation=activation, n_max=n_max),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, gdp, invp,
      jnp.asarray(v_decr, jnp.float32).reshape(1),
      jnp.asarray(seed, jnp.int32).reshape(1))
    return out[:m, :n]


# ----------------------------------------------------------- packed executor

def _stack_position(stack_index):
    """The (1,) int32 scalar-prefetch operand holding a plan's position in
    its tile stack (the first index of the tile BlockSpec's index map)."""
    return jnp.asarray(stack_index, jnp.int32).reshape(1)


def _cim_packed_kernel(pos_ref, row_ref, col_ref, x_ref, gd_ref, invn_ref,
                       den_ref, vd_ref, seed_ref, out_ref, *, v_read: float,
                       activation: str, n_max: int):
    """One grid step = one (batch block, tile) pair.

    Tiles are pre-sorted by output block (PackedPlan invariant), so all
    tiles landing in out block col_ref[t] are consecutive in t: the first
    visit zero-initializes the block, every visit accumulates the tile's
    (masked, optionally de-normalized) ADC counts — the chip's digital
    row-split partial-sum accumulation, done inside the dispatch.
    """
    t = pl.program_id(1)
    first = jnp.logical_or(
        t == 0, col_ref[jnp.maximum(t - 1, 0)] != col_ref[t])

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = jnp.dot(x_ref[...], gd_ref[0], precision=_F32,
                preferred_element_type=jnp.float32) * v_read * invn_ref[0]
    counts = _epilogue(q, vd_ref[t], activation, n_max, seed_ref,
                       ij=(pl.program_id(0), t))
    out_ref[...] += counts * _acc_weight(invn_ref[0], den_ref[0], activation)


@functools.partial(
    jax.jit,
    static_argnames=("row_block", "col_block", "activation", "n_max",
                     "v_read", "bm", "interpret"))
def cim_mvm_packed_pallas(x, gd_tiles, inv_norm_tiles, denorm_tiles,
                          v_decr_tiles, seed, stack_index, *,
                          row_block, col_block, activation: str = "none",
                          n_max: int = 127, v_read: float = 0.5,
                          bm: int = 256, interpret: bool = False):
    """Whole-layer packed CIM MVM: ONE pallas_call over every tile.

    x:(M,K) f32 integer-valued activations (K = layer weight rows);
    gd_tiles:(S,T,bk,bn) a tile stack, read at scalar position
    stack_index; inv_norm_tiles/denorm_tiles:(T,1,bn); v_decr_tiles:(T,);
    row_block/col_block: static tile->block index tuples (scalar-prefetched
    into the kernel's index maps). Returns (M_padded, n_col_blocks*bn) f32
    — caller slices to (M, C).
    """
    TRACE_COUNTS["cim_mvm_packed"] += 1
    m, kdim = x.shape
    _, n_tiles, bk, bn = gd_tiles.shape
    bm = min(bm, m)
    n_row_blocks = max(row_block) + 1
    n_col_blocks = max(col_block) + 1

    def pad(a, mults):
        pads = [(0, -s % t) for s, t in zip(a.shape, mults)]
        return jnp.pad(a, pads) if any(p[1] for p in pads) else a

    xp = pad(x, (bm, 1))
    xp = jnp.pad(xp, ((0, 0), (0, n_row_blocks * bk - kdim))) \
        if kdim < n_row_blocks * bk else xp
    mp = xp.shape[0]

    row_idx = jnp.asarray(row_block, jnp.int32)
    col_idx = jnp.asarray(col_block, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(mp // bm, n_tiles),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, t, pos, row, col: (i, row[t])),
            pl.BlockSpec((None, 1, bk, bn),
                         lambda i, t, pos, row, col: (pos[0], t, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, t, pos, row, col: (t, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda i, t, pos, row, col: (t, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, t, pos, row, col: (i, col[t])),
    )
    return pl.pallas_call(
        functools.partial(_cim_packed_kernel, v_read=v_read,
                          activation=activation, n_max=n_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n_col_blocks * bn), jnp.float32),
        interpret=interpret,
    )(_stack_position(stack_index), row_idx, col_idx, xp, gd_tiles,
      inv_norm_tiles, denorm_tiles,
      v_decr_tiles.astype(jnp.float32),
      jnp.asarray(seed, jnp.int32).reshape(1))


# --------------------------------------------------------- scheduled executor

def _cim_sched_kernel(pos_ref, row_ref, outs_ref, x_ref, gd_ref, invn_ref,
                      den_ref, vd_ref, seed_ref, out_ref, *, pass_len: int,
                      v_read: float, activation: str, n_max: int):
    """One grid step = one (batch block, pass, core slot) triple.

    Pass-major order models the chip's time-shared merged cores. Pack time
    sorted each pass's slots by output block, so slots of one output RUN
    (out_slot, prefetched) are grid-consecutive: the run's first visit
    zero-initializes the block, every visit accumulates the tile's (masked,
    optionally de-normalized) ADC counts — in-kernel digital row-split
    accumulation under the Pallas TPU consecutive-revisit VMEM rule. A slot
    opening a new run writes to a FRESH partial block, so a column block
    revisited in a later pass never reads stale memory. Idle padding slots
    have zero denorm: their all-idle runs accumulate exactly zero.
    """
    p, s = pl.program_id(1), pl.program_id(2)
    t = p * pass_len + s
    first = jnp.logical_or(
        t == 0, outs_ref[jnp.maximum(t - 1, 0)] != outs_ref[t])

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = jnp.dot(x_ref[...], gd_ref[0], precision=_F32,
                preferred_element_type=jnp.float32) * v_read * invn_ref[0]
    counts = _epilogue(q, vd_ref[t], activation, n_max, seed_ref,
                       ij=(pl.program_id(0), t))
    out_ref[...] += counts * _acc_weight(invn_ref[0], den_ref[0], activation)


def _fold_runs(parts, out_col, bn, mp):
    """Fold per-run partials into column blocks, in run order.

    The common case — every column block is exactly one run, runs in block
    order, no all-idle runs — IS the final output: return it without any
    scatter. Otherwise add each run into its block (skipping idle runs,
    out_col -1), the same left-fold order the per-slot reduction used, so
    fused and unfused execution agree bitwise on integer-valued counts.
    """
    n_col_blocks = max(c for c in out_col if c >= 0) + 1
    if out_col == tuple(range(n_col_blocks)):
        return parts
    y = jnp.zeros((mp, n_col_blocks * bn), jnp.float32)
    for r, c in enumerate(out_col):
        if c >= 0:
            y = y.at[:, c * bn:(c + 1) * bn].add(
                parts[:, r * bn:(r + 1) * bn])
    return y


@functools.partial(
    jax.jit,
    static_argnames=("row_block", "out_slot", "out_col", "n_passes",
                     "activation", "n_max", "v_read", "bm", "interpret"))
def cim_mvm_scheduled_pallas(x, gd_tiles, inv_norm_tiles, denorm_tiles,
                             v_decr_tiles, seed, stack_index, *,
                             row_block, out_slot, out_col, n_passes: int,
                             activation: str = "none", n_max: int = 127,
                             v_read: float = 0.5, bm: int = 256,
                             interpret: bool = False):
    """Whole-layer scheduled CIM MVM: ONE pallas_call over a pass-major grid.

    x:(M,K) f32 integer-valued activations; gd_tiles:(S',P*S,bk,bn) a
    stack of pass-major slot tensors in FUSED order (each pass sorted by
    output block, idle slots zeroed at the pass tail), read at scalar
    position stack_index; inv_norm_tiles/denorm_tiles:(P*S,1,bn);
    v_decr_tiles:(P*S,); row_block: static per-slot input block tuple;
    out_slot/out_col: the fused run layout (slot -> run, run -> column
    block; core/mapping._fused_layout). row_block and out_slot are
    scalar-prefetched into the kernel's index maps; the kernel accumulates
    each run in-kernel and `_fold_runs` folds only blocks split across
    runs. Returns (M_padded, n_col_blocks*bn) f32 — caller slices to
    (M, C).
    """
    TRACE_COUNTS["cim_mvm_scheduled"] += 1
    m, kdim = x.shape
    _, n_slots, bk, bn = gd_tiles.shape
    pass_len = n_slots // n_passes
    bm = min(bm, m)
    n_row_blocks = max(row_block) + 1
    n_runs = len(out_col)

    def pad(a, mults):
        pads = [(0, -s % t) for s, t in zip(a.shape, mults)]
        return jnp.pad(a, pads) if any(p[1] for p in pads) else a

    xp = pad(x, (bm, 1))
    xp = jnp.pad(xp, ((0, 0), (0, n_row_blocks * bk - kdim))) \
        if kdim < n_row_blocks * bk else xp
    mp = xp.shape[0]

    row_idx = jnp.asarray(row_block, jnp.int32)
    out_idx = jnp.asarray(out_slot, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(mp // bm, n_passes, pass_len),
        in_specs=[
            pl.BlockSpec((bm, bk),
                         lambda i, p, s, pos, row, outs:
                         (i, row[p * pass_len + s])),
            pl.BlockSpec((None, 1, bk, bn),
                         lambda i, p, s, pos, row, outs:
                         (pos[0], p * pass_len + s, 0, 0)),
            pl.BlockSpec((1, 1, bn),
                         lambda i, p, s, pos, row, outs:
                         (p * pass_len + s, 0, 0)),
            pl.BlockSpec((1, 1, bn),
                         lambda i, p, s, pos, row, outs:
                         (p * pass_len + s, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        # one partial block per RUN: a run's slots are grid-consecutive, so
        # its VMEM stays live across exactly the visits that accumulate
        # into it (the Pallas TPU consecutive-revisit invariant).
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, p, s, pos, row, outs:
                               (i, outs[p * pass_len + s])),
    )
    parts = pl.pallas_call(
        functools.partial(_cim_sched_kernel, pass_len=pass_len,
                          v_read=v_read, activation=activation, n_max=n_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n_runs * bn), jnp.float32),
        interpret=interpret,
    )(_stack_position(stack_index), row_idx, out_idx, xp, gd_tiles,
      inv_norm_tiles, denorm_tiles,
      v_decr_tiles.astype(jnp.float32),
      jnp.asarray(seed, jnp.int32).reshape(1))
    return _fold_runs(parts, out_col, bn, mp)


# -------------------------------------------------- transpose-direction executor

def _cim_transposed_kernel(pos_ref, in_ref, stk_ref, outs_ref, x_ref, gd_ref,
                           invn_ref, den_ref, vd_ref, seed_ref, out_ref, *,
                           v_read: float, activation: str, n_max: int):
    """One grid step = one (batch block, tile slot) pair, transpose direction.

    The tile block is the SAME stored (bk, bn) forward block — the shared
    conductance stack, reached through the prefetched `tile_slot` map since
    this direction's fused grid order differs from the stack's — contracted
    on its COLUMN axis (dot_general over dim 1 of both operands == x @ gd.T
    without materializing a transposed copy): the BL->SL read of the
    programmed cells. Runs of grid-consecutive same-output-block slots
    accumulate in-kernel (first visit zero-initializes); the wrapper folds
    only blocks split across runs. Stochastic draws key on the tile's
    STACK position, not the grid slot, so both directions and both fused /
    per-slot layouts sample the same per-tile stream.
    """
    t = pl.program_id(1)
    first = jnp.logical_or(
        t == 0, outs_ref[jnp.maximum(t - 1, 0)] != outs_ref[t])

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = jax.lax.dot_general(
        x_ref[...], gd_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_F32,
        preferred_element_type=jnp.float32) * v_read * invn_ref[0]
    counts = _epilogue(q, vd_ref[t], activation, n_max, seed_ref,
                       ij=(pl.program_id(0), stk_ref[t]))
    out_ref[...] += counts * _acc_weight(invn_ref[0], den_ref[0], activation)


@functools.partial(
    jax.jit,
    static_argnames=("in_block", "tile_slot", "out_slot", "out_col",
                     "activation", "n_max", "v_read", "bm", "interpret"))
def cim_mvm_transposed_pallas(x, gd_tiles, inv_norm_tiles, denorm_tiles,
                              v_decr_tiles, seed, stack_index, *,
                              in_block, tile_slot, out_slot, out_col,
                              activation: str = "none",
                              n_max: int = 127, v_read: float = 0.5,
                              bm: int = 256, interpret: bool = False):
    """Whole-layer transpose-direction CIM MVM: ONE pallas_call over the
    SHARED forward tile stack, contracted on the stored column axis.

    x:(M, K') f32 integer-valued activations (K' = the layer's weight
    COLUMNS — the transpose direction's input space); gd_tiles:(S,T,bk,bn)
    a stack of forward tile tensors, unchanged and uncopied, read at scalar
    position stack_index; inv_norm_tiles /
    denorm_tiles:(T,1,bk) transpose-direction per-ROW tensors in THIS
    direction's fused grid order (`pack_tiles_transposed`);
    v_decr_tiles:(T,) that direction's ADC steps. in_block: static per-slot
    input (forward col) block indices; tile_slot: grid slot -> forward
    stack position (the cross-direction permutation); out_slot/out_col:
    the fused run layout (core/mapping._fused_layout) over transpose-
    direction output (forward row) blocks. Runs accumulate in-kernel;
    `_fold_runs` folds only blocks split across runs. Returns
    (M_padded, n_out_blocks*bk) f32 — caller slices to (M, R).
    """
    TRACE_COUNTS["cim_mvm_transposed"] += 1
    m, kdim = x.shape
    _, n_slots, bko, bni = gd_tiles.shape  # stored fwd layout: out/in swap
    bm = min(bm, m)
    n_in_blocks = max(in_block) + 1
    n_runs = len(out_col)

    def pad(a, mults):
        pads = [(0, -s % t) for s, t in zip(a.shape, mults)]
        return jnp.pad(a, pads) if any(p[1] for p in pads) else a

    xp = pad(x, (bm, 1))
    xp = jnp.pad(xp, ((0, 0), (0, n_in_blocks * bni - kdim))) \
        if kdim < n_in_blocks * bni else xp
    mp = xp.shape[0]

    in_idx = jnp.asarray(in_block, jnp.int32)
    stk_idx = jnp.asarray(tile_slot, jnp.int32)
    out_idx = jnp.asarray(out_slot, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(mp // bm, n_slots),
        in_specs=[
            pl.BlockSpec((bm, bni),
                         lambda i, t, pos, inb, stk, outs: (i, inb[t])),
            pl.BlockSpec((None, 1, bko, bni),
                         lambda i, t, pos, inb, stk, outs:
                         (pos[0], stk[t], 0, 0)),
            pl.BlockSpec((1, 1, bko),
                         lambda i, t, pos, inb, stk, outs: (t, 0, 0)),
            pl.BlockSpec((1, 1, bko),
                         lambda i, t, pos, inb, stk, outs: (t, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bko),
                               lambda i, t, pos, inb, stk, outs:
                               (i, outs[t])),
    )
    parts = pl.pallas_call(
        functools.partial(_cim_transposed_kernel, v_read=v_read,
                          activation=activation, n_max=n_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, n_runs * bko), jnp.float32),
        interpret=interpret,
    )(_stack_position(stack_index), in_idx, stk_idx, out_idx, xp, gd_tiles,
      inv_norm_tiles, denorm_tiles,
      v_decr_tiles.astype(jnp.float32),
      jnp.asarray(seed, jnp.int32).reshape(1))
    return _fold_runs(parts, out_col, bko, mp)

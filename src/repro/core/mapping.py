"""TNSA multi-core weight mapping — the PLAN, SCHEDULE and PACK stages of the
chip-compiler pipeline (paper Fig. 2a + Methods 'Weight mapping strategy onto
multiple CIM cores'; see DESIGN.md 'Chip-compiler pipeline').

A NeuRRAM chip has 48 cores of 256x256 cells; a weight matrix is first turned
into a conductance matrix (differential rows double the height: 2R x C, plus
bias rows), then the deployment stack runs an explicit compiler pipeline —
``plan -> schedule -> program -> calibrate -> pack`` — whose first, second
and fifth stages live here:

  * `plan_layers` (stage 1, PLAN): the paper's allocation policy —
    matrices larger than a core are SPLIT into <=256x256 tiles; hot
    matrices are DUPLICATED across spare cores (data parallelism); small
    matrices are MERGED diagonally (parallel access) or horizontally
    (sequential access, `seq_slot` > 0); and wide matrices are SPLIT
    VERTICALLY to bound IR drop — `ir_drop_max_cols` derives the
    `max_cols_per_core` constraint from `NonIdealityConfig.ir_drop_alpha`.
  * `schedule_tiles` (stage 2, SCHEDULE): serializes same-core `seq_slot`
    tiles into ordered PASSES — the chip time-shares a merged core, so its
    occupants cannot fire together — while tiles on different cores overlap
    within a pass. The result is a pass-major execution order (+ idle-slot
    padding) the packed kernel consumes as a pass grid dimension.
  * `pack_tiles` (stage 5, PACK): the (scheduled) tile plan as DATA, not
    control flow. All tiles of a layer are gathered into padded stacked
    tensors (`gd_tiles (T, bk, bn)`, `inv_norm_tiles (T, 1, bn)`,
    `v_decr_tiles (T,)`, `denorm_tiles (T, 1, bn)`) plus static
    `row_block/col_block` index tuples, and the whole layer executes as
    ONE Pallas dispatch (`kernels/cim_mvm`) with row-split partial sums
    accumulated digitally inside the kernel via output-block index maps.
    Pack time computes the FUSED slot layout (`_fused_layout`): each
    pass's slots are stably re-sorted by output column block so tiles
    landing in the same block become CONSECUTIVE grid visits (runs) that
    accumulate in-kernel; only a block genuinely revisited in a later
    pass falls back to a per-run partial the wrapper folds after the
    dispatch (`out_slot`/`out_col`). The stable within-pass sort keeps
    every block's accumulation order identical to the pass-major order —
    the design intent is bitwise equality between fused and
    per-slot-partial execution, and the layout invariants that intent
    rests on (runs genuinely consecutive, every output block covered
    exactly once, index maps in bounds) are not taken on faith: the
    chip-IR verifier (`core.verify.check_packed`, run by
    `compile_chip(verify="strict")` and at every deploy surface) checks
    them statically on the emitted artifact, and the parity tests pin
    the equality on the executed kernels.
  * `pack_tiles_transposed` (stage 5, transpose direction): the BL->SL
    view of the same plan for bidirectional workloads (paper Fig. 4e-g
    RBM Gibbs sampling). It REUSES the forward pack's gd_tiles stack —
    one programmed conductance set, two directions — and only builds the
    per-direction normalizer / ADC-step / denorm tensors (the transpose
    direction normalizes by per-tile ROW sums and carries its own
    calibration); `transpose_tiles` gives the matching per-tile view for
    the loop executor and calibration.

Stages 3 and 4 (PROGRAM, CALIBRATE) live in `core.cim`, which composes all
five into `compile_chip` -> `CompiledChip`, the artifact `CIMEngine` and
`models/nn.deploy_packed_stack` serve from.

Execution comes in two forms:

  * `multicore_mvm` — the legacy per-tile Python loop (one `dynamic_slice`
    matmul per tile). Kept as the readable reference executor; it retraces
    per tile shape and cannot be folded into a serving-path jit cheaply.
  * `multicore_mvm_packed` — a packed plan through the single-dispatch
    Pallas executor: unscheduled single-pass plans take the tile-grid
    kernel, scheduled multi-pass plans the pass-major grid kernel.

A `PackedPlan` is a pytree whose geometry (tile index maps, block sizes,
pass structure) is static aux data: packed plans of a scanned layer stack
can be stacked with `tree_map(jnp.stack, ...)` and scanned without
retracing, each layer's kernels reading their tiles in place from the
stack (`split_tile_stacks`). At datacenter scale the planner operates per
TP shard (a 'core' is the intra-shard unit; see
distributed/sharding.shard_shape).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import CIMConfig, CoreSpec


@dataclasses.dataclass
class Tile:
    layer: str
    row0: int          # offset in the layer's conductance-row space (weight rows)
    col0: int
    rows: int
    cols: int
    core: int = -1     # assigned physical core
    replica: int = 0   # >0 for duplicated tiles
    seq_slot: int = 0  # >0 => shares a core with other tiles, accessed serially


@dataclasses.dataclass
class MatrixReq:
    name: str
    rows: int               # weight rows (pre-differential)
    cols: int
    intensity: float = 1.0  # compute per weight (MACs/weight) — duplication prio


@dataclasses.dataclass(eq=False)      # identity hash: Plan rides pytree aux
class Plan:
    tiles: List[Tile]
    n_cores_used: int
    duplicated: Dict[str, int]
    merged: List[Tuple[str, ...]]

    def tiles_for(self, name: str) -> List[Tile]:
        return [t for t in self.tiles if t.layer == name and t.replica == 0]


def ir_drop_max_cols(cfg: CIMConfig, spec: CoreSpec = CoreSpec(),
                     droop_tol: float = 0.05) -> Optional[int]:
    """IR-drop planning constraint (paper Methods 'Weight mapping strategy':
    wide matrices are split vertically across cores to limit IR drop).

    Mirrors the oracle's droop model (kernels/cim_mvm/ref.py `settle`):
    the driver droop per pulse phase is `ir_drop_alpha` (1/uS) times the
    TOTAL current load — every active row wire sources its whole row of
    differential pairs, so a core of R weight rows and C columns sees at
    worst R * C * (g_max + g_min) of activated conductance. Cap the
    columns per core so that worst-case droop alpha * R * C * (g_max +
    g_min) stays under `droop_tol` (5% — within what per-core ADC
    calibration absorbs; real input patterns drive fewer rows, so the
    residual is smaller still). Returns None when ir_drop is off (no
    constraint).
    """
    alpha = cfg.nonideal.ir_drop_alpha
    if alpha <= 0:
        return None
    rows = spec.rows // 2                          # differential weight rows
    g_pair = cfg.device.g_max + cfg.device.g_min   # worst-case G+ + G- /cell
    return max(1, min(spec.cols, int(droop_tol / (alpha * rows * g_pair))))


def plan_layers(reqs: Sequence[MatrixReq], spec: CoreSpec = CoreSpec(),
                differential_rows: bool = True,
                max_cols_per_core: Optional[int] = None) -> Plan:
    """Stage 1 (PLAN): greedy reproduction of the paper's allocation policy.

    max_cols_per_core: optional vertical-split constraint (IR drop) — tiles
    never exceed this many columns; see `ir_drop_max_cols`.
    """
    row_cap = spec.rows // 2 if differential_rows else spec.rows  # 128 weights
    col_cap = spec.cols
    if max_cols_per_core is not None:
        col_cap = max(1, min(col_cap, max_cols_per_core))

    # 1) split every matrix into tiles
    per_layer: List[List[Tile]] = []
    for r in reqs:
        tiles = []
        for i in range(math.ceil(r.rows / row_cap)):
            for j in range(math.ceil(r.cols / col_cap)):
                tiles.append(Tile(
                    layer=r.name, row0=i * row_cap, col0=j * col_cap,
                    rows=min(row_cap, r.rows - i * row_cap),
                    cols=min(col_cap, r.cols - j * col_cap)))
        per_layer.append(tiles)

    all_tiles = [t for ts in per_layer for t in ts]
    n = len(all_tiles)
    merged: List[Tuple[str, ...]] = []

    if n > spec.n_cores:
        # 3)/4) merge: group low-intensity, narrow tiles. Greedy first-fit by
        # (a) diagonal merge if rows+rows<=cap and cols+cols<=cap (parallel),
        # (b) horizontal merge (sequential) otherwise.
        inten = {r.name: r.intensity for r in reqs}
        order = sorted(range(n), key=lambda i: (inten[all_tiles[i].layer],
                                                all_tiles[i].rows *
                                                all_tiles[i].cols))
        groups: List[List[int]] = []
        placed = [False] * n
        # keep high-intensity tiles un-merged (paper: avoid merging hot layers)
        budget_excess = n - spec.n_cores
        for idx in order:
            if placed[idx]:
                continue
            group = [idx]
            placed[idx] = True
            if budget_excess > 0:
                for jdx in order:
                    if placed[jdx] or budget_excess <= 0:
                        continue
                    rs = sum(all_tiles[g].rows for g in group) + all_tiles[jdx].rows
                    cs = sum(all_tiles[g].cols for g in group) + all_tiles[jdx].cols
                    diag_ok = rs <= row_cap and cs <= col_cap
                    horiz_ok = (all_tiles[jdx].rows == all_tiles[group[0]].rows
                                and len(group) < 4)
                    if diag_ok or horiz_ok:
                        group.append(jdx)
                        placed[jdx] = True
                        budget_excess -= 1
            groups.append(group)
        if len(groups) > spec.n_cores:
            raise ValueError(
                f"model needs {len(groups)} cores > {spec.n_cores} available")
        for gi, group in enumerate(groups):
            if len(group) > 1:
                merged.append(tuple(all_tiles[g].layer for g in group))
            for slot, g in enumerate(group):
                all_tiles[g].core = gi
                all_tiles[g].seq_slot = slot
        n_used = len(groups)
        dup: Dict[str, int] = {}
    else:
        for ci, t in enumerate(all_tiles):
            t.core = ci
        n_used = n
        # 2) duplicate hottest layers into spare cores (data parallelism)
        dup = {}
        spare = spec.n_cores - n_used
        by_heat = sorted(reqs, key=lambda r: -r.intensity)
        extra: List[Tile] = []
        for r in by_heat:
            if spare <= 0 or r.intensity <= 1.0:
                break
            base = [t for t in all_tiles if t.layer == r.name]
            copies = min(spare // max(len(base), 1),
                         max(int(r.intensity) - 1, 0))
            for c in range(copies):
                # budget invariant: a whole replica fits in the remaining
                # spare cores. min() above implies it; assert rather than
                # silently under-duplicate if planner edits ever break it
                # (regression: test_duplication_respects_core_budget).
                assert spare >= len(base), \
                    f"replica overruns core budget ({spare=} < {len(base)=})"
                for t in base:
                    extra.append(dataclasses.replace(
                        t, core=spec.n_cores - spare, replica=c + 1))
                    spare -= 1
            if copies:
                dup[r.name] = copies
        all_tiles += extra
        n_used = spec.n_cores - spare

    return Plan(tiles=all_tiles, n_cores_used=n_used, duplicated=dup,
                merged=merged)


# ------------------------------------------------------------- stage 2: schedule

@dataclasses.dataclass(frozen=True)
class TileSchedule:
    """Stage 2 (SCHEDULE) artifact: one layer's tiles serialized into ordered
    passes the way the chip time-shares merged cores (Fig. 2a sequential
    access).

    order: pass-major slot -> index into the layer's replica-0 tile list
           (None = idle slot: the pass has fewer tiles than `pass_len`,
           i.e. some cores sit out this pass).
    n_passes: number of sequential passes (= number of distinct seq_slots).
    pass_len: tiles (cores firing) per pass, after padding to the widest pass.
    """
    order: Tuple[Optional[int], ...]
    n_passes: int
    pass_len: int


def schedule_tiles(tiles: Sequence[Tile]) -> TileSchedule:
    """Serialize same-core `seq_slot` tiles into ordered passes.

    Tiles sharing a core (seq_slot > 0 from the planner's sequential merge)
    cannot fire together — the chip accesses a merged core's occupants
    serially — but tiles on DIFFERENT cores overlap within a pass. Pass p
    holds every tile whose (rank-normalized) seq_slot is p, sorted by output
    then input block so row-split partial sums accumulate in the loop
    executor's order; narrower passes are padded with idle slots.
    """
    tiles = [t for t in tiles if t.replica == 0]
    if not tiles:
        raise ValueError("schedule_tiles needs at least one tile")
    slots = sorted({t.seq_slot for t in tiles})
    rank = {s: i for i, s in enumerate(slots)}
    passes: List[List[int]] = [[] for _ in slots]
    for i, t in enumerate(tiles):
        passes[rank[t.seq_slot]].append(i)
    for p in passes:
        p.sort(key=lambda i: (tiles[i].col0, tiles[i].row0))
    pass_len = max(len(p) for p in passes)
    order: List[Optional[int]] = []
    for p in passes:
        order += p + [None] * (pass_len - len(p))
    return TileSchedule(order=tuple(order), n_passes=len(passes),
                        pass_len=pass_len)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedPlan:
    """One layer's tile plan as data: padded stacked tile tensors + static
    index maps, executable as a single Pallas dispatch.

    Arrays (pytree children — may carry extra leading dims when plans of a
    scanned layer stack are stacked together):
      gd_tiles:       (T, bk, bn) zero-padded per-tile matrix blocks (raw
                      weights, or folded differential conductances G+ - G-).
      inv_norm_tiles: (T, 1, bn)  per-tile per-column voltage-mode normalizer
                      1/sum(G+ + G-); 0 in padded columns; 1 for raw matmuls.
      v_decr_tiles:   (T,)        per-tile ADC charge-decrement step.
      denorm_tiles:   (T, 1, bn)  digital accumulation factor applied to each
                      tile's ADC counts before the row-split partial-sum add:
                      mask only (loop-executor count semantics) or
                      mask * norm * v_decr (de-normalized charge units, the
                      chip's digital post-processing folded into the kernel).

    Static geometry (pytree aux — hashable, shared by all stacked layers):
      row_block/col_block: slot index -> input/output block index. Unscheduled
                      plans are sorted so tiles of one output block are
                      contiguous; scheduled plans are PASS-MAJOR (pass p's
                      tiles occupy slots [p*pass_len, (p+1)*pass_len)) with
                      idle slots pointing at block 0.
      seq_slot:       per-slot pass index (0 for unscheduled plans).
      n_passes:       pass count; > 1 routes execution to the pass-major
                      scheduled kernel (kernels/cim_mvm), which accumulates
                      each output RUN in-kernel (see out_slot/out_col).
      tile_slot:      slot index -> position in the gd_tiles STACK. Identity
                      for forward plans (tensors are built in grid order);
                      a transpose-direction plan has its own fused grid
                      order but indexes the SHARED forward stack, so its
                      tile_slot is the cross-direction permutation
                      (scalar-prefetched into the kernel's gd index map).
      out_slot/out_col: the fused-reduction layout (`_fused_layout`):
                      out_slot maps slot -> output RUN index, out_col maps
                      run -> output column block (-1 for all-idle runs).
                      A run is a maximal stretch of grid-consecutive slots
                      sharing one output block; the kernel accumulates each
                      run in VMEM and the wrapper folds only blocks split
                      across runs (genuine non-consecutive revisits).
      transpose:      True for a TRANSPOSE-DIRECTION plan
                      (`pack_tiles_transposed`): gd_tiles are SHARED with the
                      forward plan (stored (T, bn, bk), i.e. transposed
                      relative to this plan's logical input/output blocks)
                      and execution routes to the transpose-direction kernel,
                      which contracts each tile on its stored COLUMN axis —
                      the TNSA's BL->SL access of the same programmed cells.

    Reading a tile stack in place (`split_tile_stacks` / `join_tile_stacks`):
      stack_index:    None for a plan that owns its tiles. Otherwise gd_tiles
                      is a whole scanned stack (*S, T, bk, bn) and stack_index
                      an int32 array over the leading dims the plan still has
                      (() once one layer and shard remain) holding flat
                      positions in S. Scans and shard loops index stack_index
                      and leave gd_tiles whole (`take`); the kernels merge S
                      into one axis (a bitcast) and start each tile's DMA at
                      the prefetched position, so no layer's tiles are copied
                      out of the stack before the kernel reads them.
    """
    layer: str
    bk: int
    bn: int
    n_rows: int
    n_cols: int
    row_block: Tuple[int, ...]
    col_block: Tuple[int, ...]
    seq_slot: Tuple[int, ...]
    n_passes: int
    transpose: bool
    tile_slot: Tuple[int, ...]
    out_slot: Tuple[int, ...]
    out_col: Tuple[int, ...]
    gd_tiles: jax.Array
    inv_norm_tiles: jax.Array
    v_decr_tiles: jax.Array
    denorm_tiles: jax.Array
    stack_index: Optional[jax.Array] = None

    @property
    def n_tiles(self) -> int:
        return len(self.row_block)

    @property
    def pass_len(self) -> int:
        return self.n_tiles // self.n_passes

    @property
    def n_row_blocks(self) -> int:
        return max(self.row_block) + 1

    @property
    def n_col_blocks(self) -> int:
        return max(self.col_block) + 1

    def tree_flatten(self):
        children = (self.gd_tiles, self.inv_norm_tiles, self.v_decr_tiles,
                    self.denorm_tiles, self.stack_index)
        aux = (self.layer, self.bk, self.bn, self.n_rows, self.n_cols,
               self.row_block, self.col_block, self.seq_slot, self.n_passes,
               self.transpose, self.tile_slot, self.out_slot, self.out_col)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*aux, *children)


def _is_plan(x) -> bool:
    return isinstance(x, PackedPlan)


def split_tile_stacks(tree):
    """Take every packed plan's tile stack out of a layer-stack tree.

    Returns (tree, stacks): each plan keeps its small per-tile arrays and
    gains a stack_index over its leading dims (flat positions in the stack;
    a plan that already has one keeps it), and its gd_tiles leave the tree
    for the `stacks` list. Scan the returned tree and close over `stacks`:
    the scan then slices only the index, and `join_tile_stacks` hands each
    layer's plans the whole stack back (`transformer.scan_layers`)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_plan)
    stacks = []
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, PackedPlan):
            idx = leaf.stack_index
            if idx is None:
                lead = leaf.gd_tiles.shape[:-3]
                idx = jnp.arange(math.prod(lead), dtype=jnp.int32) \
                    .reshape(lead)
            stacks.append(leaf.gd_tiles)
            leaves[i] = dataclasses.replace(leaf, gd_tiles=None,
                                            stack_index=idx)
    return treedef.unflatten(leaves), stacks


def join_tile_stacks(tree, stacks):
    """Inverse of `split_tile_stacks` on a tree of the same structure (a
    scan step's slice of it included): plan i gets stacks[i] back whole."""
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_plan)
    it = iter(stacks)
    leaves = [dataclasses.replace(leaf, gd_tiles=next(it))
              if isinstance(leaf, PackedPlan) else leaf for leaf in leaves]
    return treedef.unflatten(leaves)


def take(tree, i):
    """`tree[i]` on the leading dim of every array in `tree`, except that a
    stack-indexed plan keeps its tile stack whole: its stack_index is
    indexed instead (the shard and expert loops over packed plans)."""
    def one(a):
        if isinstance(a, PackedPlan) and a.stack_index is not None:
            sub = jax.tree_util.tree_map(
                lambda x: x[i], dataclasses.replace(a, gd_tiles=None))
            return dataclasses.replace(sub, gd_tiles=a.gd_tiles)
        return jax.tree_util.tree_map(lambda x: x[i], a)
    return jax.tree_util.tree_map(one, tree, is_leaf=_is_plan)


def slice_tile_stacks(tree):
    """Give every stack-indexed plan in `tree` its own tiles again: the
    slice of the stack its stack_index spans, and no index. For executors
    that split a plan's leading dims across devices (shard_map), where the
    stack's shard dim must stay a dim of its own.

    stack_index spans the trailing dims of the stack's leading dims whole
    (scans and `take` only ever index its leading dim), so its first entry
    divided by its size is the position of that slice."""
    def one(a):
        if not isinstance(a, PackedPlan) or a.stack_index is None:
            return a
        idx = a.stack_index
        gd = a.gd_tiles.reshape((-1,) + idx.shape + a.gd_tiles.shape[-3:])
        start = idx.reshape(-1)[0] // idx.size
        return dataclasses.replace(
            a, gd_tiles=jax.lax.dynamic_index_in_dim(gd, start, 0,
                                                     keepdims=False),
            stack_index=None)
    return jax.tree_util.tree_map(one, tree, is_leaf=_is_plan)


def _slot_order(tiles: Sequence[Tile], schedule: Optional[TileSchedule]
                ) -> Tuple[List[Optional[int]], int, int]:
    """The slot -> tile-index order a (scheduled) pack executes in.

    Shared by `pack_tiles` and `pack_tiles_transposed` so both directions of
    one programmed array agree slot-for-slot (the transpose-direction pack
    indexes the forward direction's gd_tiles stack by slot). Returns
    (order, n_passes, pass_len); idle slots are None.
    """
    if schedule is None:
        order: List[Optional[int]] = sorted(
            range(len(tiles)),
            key=lambda i: (tiles[i].col0, tiles[i].row0, tiles[i].seq_slot))
        return order, 1, len(tiles)
    # the non-idle slots must be exactly a permutation of the tiles —
    # a bare count check would let a duplicated index pack one tile
    # twice while silently dropping another
    covered = sorted(i for i in schedule.order if i is not None)
    if covered != list(range(len(tiles))):
        raise ValueError("schedule does not cover this tile sequence "
                         f"exactly once ({schedule.order=} vs "
                         f"{len(tiles)} tiles)")
    return list(schedule.order), schedule.n_passes, schedule.pass_len


def _fused_layout(blocks: Sequence[Optional[int]], pass_len: int
                  ) -> Tuple[List[int], Tuple[int, ...], Tuple[int, ...]]:
    """Fused slot layout: re-sort each pass's slots by output block.

    blocks: per-slot output block index in pass-major order (None = idle).
    Returns (perm, out_slot, out_col):
      perm:     grid position -> original slot position. Each pass is sorted
                STABLY by output block (idle slots to the pass tail), never
                across passes — same-block slots keep their relative order,
                so every output block's accumulation order (and hence the
                float result) is unchanged; only the grouping into grid
                visits moves.
      out_slot: grid position -> output RUN index. A run is a maximal
                stretch of grid-CONSECUTIVE positions sharing one output
                block (it may span a pass boundary): the kernel accumulates
                a whole run in the output block's VMEM — exactly the
                visits the Pallas TPU liveness rule keeps alive — and
                emits ONE partial per run.
      out_col:  run index -> output column block (-1 = all-idle run, whose
                exact-zero partial the wrapper drops). A block revisited
                NON-consecutively (a later pass, other blocks in between)
                spans several runs and falls back to the post-dispatch fold
                for those runs only.
    """
    perm: List[int] = []
    for p0 in range(0, len(blocks), pass_len):
        chunk = list(range(p0, min(p0 + pass_len, len(blocks))))
        chunk.sort(key=lambda i: (1, 0) if blocks[i] is None
                   else (0, blocks[i]))
        perm += chunk
    out_slot: List[int] = []
    out_col: List[int] = []
    for pos in perm:
        blk = -1 if blocks[pos] is None else blocks[pos]
        if not out_col or out_col[-1] != blk:
            out_col.append(blk)
        out_slot.append(len(out_col) - 1)
    return perm, tuple(out_slot), tuple(out_col)


def transpose_tiles(tiles: Sequence[Tile]) -> List[Tile]:
    """The SAME physical tiles viewed in the transpose (BL->SL) direction:
    row/col offsets and extents swap, while core / replica / seq_slot — the
    physical placement — are untouched. This is the tile-level statement of
    TNSA bidirectionality: one programmed core region, two access
    orientations. Used by the transpose-direction loop executor (parity
    reference) and per-direction calibration."""
    return [dataclasses.replace(t, row0=t.col0, col0=t.row0,
                                rows=t.cols, cols=t.rows) for t in tiles]


def pack_tiles(tiles: Sequence[Tile], gd, *, gsum=None, v_decr=1.0,
               fold_norm: bool = False,
               schedule: Optional[TileSchedule] = None) -> PackedPlan:
    """Stage 5 (PACK): gather one layer's (scheduled) tiles into a PackedPlan.

    gd: (R, C) matrix in weight-row space — a raw weight matrix for the
        generic executor, or folded differential conductances G+ - G- for the
        CIM datapath.
    gsum: optional (R, C) G+ + G- whose per-tile column sums give the
        voltage-mode normalizer; None means normalizer 1 (raw matmul).
    v_decr: scalar, or (T,) per-tile ADC decrement steps aligned with the
        replica-0 tiles in the ORDER GIVEN (reordered internally together
        with the tiles; ignored by raw matmuls).
    fold_norm: fold mask * norm * v_decr into denorm_tiles so the packed
        kernel's digital accumulation directly yields de-normalized charge
        units (CIMEngine's serving path); False keeps raw summed counts
        (bitwise-comparable with the per-tile loop executor).
    schedule: optional TileSchedule from `schedule_tiles` over the SAME tile
        sequence — orders slots pass-major and pads idle slots with inert
        zero tiles (denorm 0). None packs a single-pass plan in output-block
        order (the PR-1 tile-grid layout).
    """
    tiles = [t for t in tiles if t.replica == 0]
    if not tiles:
        raise ValueError("pack_tiles needs at least one tile")
    bk = max(t.rows for t in tiles)
    bn = max(t.cols for t in tiles)
    for t in tiles:
        if t.row0 % bk or t.col0 % bn:
            raise ValueError(
                f"tile offsets ({t.row0},{t.col0}) not aligned to "
                f"({bk},{bn}) blocks — not a splitter-produced plan")
    order, n_passes, pass_len = _slot_order(tiles, schedule)
    blocks = [None if i is None else tiles[i].col0 // bn for i in order]
    perm, out_slot, out_col = _fused_layout(blocks, pass_len)
    order = [order[p] for p in perm]
    v_decr = jnp.broadcast_to(jnp.asarray(v_decr, jnp.float32),
                              (len(tiles),))
    n_rows = max(t.row0 + t.rows for t in tiles)
    n_cols = max(t.col0 + t.cols for t in tiles)

    # per-slot geometry (idle slots: an empty extent -> zero tile)
    live = [i is not None for i in order]
    pick = lambda f: np.array([f(tiles[i]) if i is not None else 0
                               for i in order], np.int32)
    r0, c0 = pick(lambda t: t.row0), pick(lambda t: t.col0)
    rows, cols = pick(lambda t: t.rows), pick(lambda t: t.cols)
    col_ok = np.arange(bn)[None, :] < cols[:, None]                # (T, bn)
    cell_ok = (np.arange(bk)[None, :, None] < rows[:, None, None]) \
        & col_ok[:, None, :]                                       # (T,bk,bn)
    gd_tiles = jnp.where(cell_ok, _gather_blocks(gd, r0, c0, bk, bn), 0.0)
    mask = jnp.asarray(col_ok, jnp.float32)
    if gsum is None:
        inv = norm = mask                   # normalizer 1 on valid columns
    else:
        norm = jnp.sum(jnp.where(cell_ok, _gather_blocks(gsum, r0, c0,
                                                         bk, bn), 0.0),
                       axis=1)
        inv = jnp.where(norm > 0, 1.0 / jnp.maximum(norm, 1e-30), 0.0)
    vd_slots = jnp.where(jnp.asarray(live),
                         v_decr[np.array([i or 0 for i in order])], 1.0)
    den = (mask * norm * vd_slots[:, None]) if fold_norm else mask

    return PackedPlan(
        layer=tiles[0].layer, bk=bk, bn=bn, n_rows=n_rows, n_cols=n_cols,
        row_block=tuple(int(r) // bk for r in r0),
        col_block=tuple(int(c) // bn for c in c0),
        seq_slot=tuple(si // pass_len for si in range(len(order))),
        n_passes=n_passes,
        transpose=False,
        tile_slot=tuple(range(len(order))),
        out_slot=out_slot,
        out_col=out_col,
        gd_tiles=gd_tiles,
        inv_norm_tiles=inv[:, None, :],
        v_decr_tiles=vd_slots,
        denorm_tiles=den[:, None, :])


def _gather_blocks(a, r0, c0, bk: int, bn: int):
    """(T, bk, bn) windows of the 2-D `a` at offsets (r0[t], c0[t]), read
    past the matrix edge as zeros — one gather for a whole layer's tiles
    instead of one slice per tile (thousands of dispatches at published
    widths)."""
    a = jnp.pad(jnp.asarray(a, jnp.float32), ((0, bk), (0, bn)))
    return jax.vmap(lambda r, c: jax.lax.dynamic_slice(a, (r, c), (bk, bn)))(
        jnp.asarray(r0), jnp.asarray(c0))


def pack_tiles_transposed(tiles: Sequence[Tile], packed: PackedPlan, *,
                          gsum=None, v_decr=1.0, fold_norm: bool = False,
                          schedule: Optional[TileSchedule] = None
                          ) -> PackedPlan:
    """Stage 5 (PACK), transpose direction: the BL->SL view of a packed plan.

    The TNSA runs MVMs in both directions on ONE programmed conductance set,
    so the transpose-direction pack does NOT copy the conductances: it
    reuses `packed.gd_tiles` (the forward stack, by reference) and only
    builds the per-direction small tensors — the voltage-mode normalizer of
    the transpose direction (per-tile ROW sums of G+ + G-, since the roles
    of input and output wires swap), the per-tile ADC steps from the
    transpose direction's own calibration, and the matching denorm factors.

    tiles / schedule: the SAME forward-space inputs given to `pack_tiles`
    (slot order is recomputed identically, so slot s of this plan is the
    transpose view of slot s of `packed`).
    gsum: (R, C) G+ + G- in the FORWARD orientation; None means raw matmul.
    v_decr: scalar or (T,) transpose-direction ADC steps aligned with the
    replica-0 tiles in the order given.

    The result is a PackedPlan in the transpose direction's OWN logical
    space (n_rows/n_cols, row/col block maps and block sizes all swapped)
    with `transpose=True`, which routes execution to the transpose-direction
    kernel (`kernels/cim_mvm.cim_mvm_transposed_pallas`).
    """
    tiles = [t for t in tiles if t.replica == 0]
    if not tiles:
        raise ValueError("pack_tiles_transposed needs at least one tile")
    if packed.transpose:
        raise ValueError("packed must be the forward-direction plan")
    order, n_passes, pass_len = _slot_order(tiles, schedule)
    if len(order) != packed.n_tiles or n_passes != packed.n_passes:
        raise ValueError(
            f"tiles/schedule do not match the forward pack "
            f"({len(order)} slots vs {packed.n_tiles}, "
            f"{n_passes} passes vs {packed.n_passes})")
    bk_f, bn_f = packed.bk, packed.bn
    # the forward pack built gd_tiles in ITS fused grid order; reproduce that
    # permutation to locate each slot in the shared stack, then fuse THIS
    # direction's grid by its own output blocks (forward ROW blocks). The
    # kernel indexes gd_tiles through tile_slot — no copy, no permuted stack.
    blocks_f = [None if i is None else tiles[i].col0 // bn_f for i in order]
    perm_f, _, _ = _fused_layout(blocks_f, pass_len)
    stack_pos = {p: g for g, p in enumerate(perm_f)}
    blocks_b = [None if i is None else tiles[i].row0 // bk_f for i in order]
    perm_b, out_slot, out_col = _fused_layout(blocks_b, pass_len)
    tile_slot = tuple(stack_pos[p] for p in perm_b)
    order = [order[p] for p in perm_b]
    v_decr = jnp.broadcast_to(jnp.asarray(v_decr, jnp.float32),
                              (len(tiles),))
    zero_out = jnp.zeros((bk_f,), jnp.float32)   # transpose output block
    inv_tiles, den_tiles, vd_slots = [], [], []
    for idx in order:
        if idx is None:                    # idle slot: a core sits out
            inv_tiles.append(zero_out)
            den_tiles.append(zero_out)     # accumulates exactly zero
            vd_slots.append(jnp.asarray(1.0, jnp.float32))
            continue
        t = tiles[idx]
        mask = zero_out.at[:t.rows].set(1.0)
        if gsum is None:
            inv = mask                     # normalizer 1 on valid rows
            norm = mask
        else:
            norm_t = jnp.sum(jax.lax.dynamic_slice(
                gsum, (t.row0, t.col0), (t.rows, t.cols)), axis=1)
            norm = zero_out.at[:t.rows].set(norm_t)
            inv = jnp.where(norm > 0, 1.0 / jnp.maximum(norm, 1e-30), 0.0)
        den_tiles.append((mask * norm * v_decr[idx]) if fold_norm else mask)
        inv_tiles.append(inv)
        vd_slots.append(v_decr[idx])

    return PackedPlan(
        layer=packed.layer, bk=bn_f, bn=bk_f,
        n_rows=packed.n_cols, n_cols=packed.n_rows,
        row_block=tuple(packed.col_block[g] for g in tile_slot),
        col_block=tuple(packed.row_block[g] for g in tile_slot),
        seq_slot=packed.seq_slot,
        n_passes=n_passes,
        transpose=True,
        tile_slot=tile_slot,
        out_slot=out_slot,
        out_col=out_col,
        gd_tiles=packed.gd_tiles,          # SHARED — one conductance set
        inv_norm_tiles=jnp.stack(inv_tiles)[:, None, :],
        v_decr_tiles=jnp.stack(vd_slots),
        denorm_tiles=jnp.stack(den_tiles)[:, None, :])


def multicore_mvm_packed(x, packed: PackedPlan, cfg=None, *, seed=0,
                         interpret=None, scheduled=None, fused: bool = True):
    """Execute a whole layer's tile plan in ONE compiled Pallas dispatch.

    cfg=None: exact tiled matmul (identity epilogue) — returns x @ W in f32,
    bitwise-stable under the zero padding. With a CIMConfig: the full CIM
    datapath (quantized ADC counts accumulated per denorm_tiles semantics).
    Row-split partial sums accumulate digitally inside the kernel via
    output-block index maps; there is no Python loop and a single jit trace
    per plan shape. Multi-pass (seq-slot scheduled) plans take the
    pass-major grid kernel automatically; `scheduled` forces either kernel
    (benchmark use). Transpose-direction plans (`pack_tiles_transposed`,
    packed.transpose=True) always take the transpose-direction kernel —
    `scheduled` is ignored. `fused=False` forces the per-slot-partial
    reduction layout (pre-fusion baseline; bitwise-equal on integer counts).
    """
    from ..kernels.cim_mvm.ops import cim_mvm_packed, packed_call
    if cfg is not None:
        return cim_mvm_packed(x, packed, cfg, seed=seed, interpret=interpret,
                              scheduled=scheduled, fused=fused)
    return packed_call(x, packed, activation="identity", n_max=1,
                       v_read=1.0, seed=seed, interpret=interpret,
                       scheduled=scheduled, fused=fused)


def multicore_mvm(x, weight, plan_tiles: Sequence[Tile], matmul_fn):
    """Execute y = x @ weight tile-by-tile with digital partial sums.

    The legacy per-tile LOOP executor, kept as the readable reference (and
    for exotic per-tile matmul_fn experiments). It emits one dynamic_slice
    matmul per tile — use pack_tiles + multicore_mvm_packed on hot paths.

    matmul_fn(x_tile, w_tile, tile) -> (B, tile.cols) performs one core's CIM
    MVM (any mode: exact / noisy / chip-sim). Row-split partial sums are
    accumulated digitally (the chip gives partial sums 2 extra output bits;
    we accumulate in f32 which dominates that).
    """
    b = x.shape[0]
    cols = weight.shape[1]
    y = jnp.zeros((b, cols), jnp.float32)
    for t in plan_tiles:
        xt = jax.lax.dynamic_slice(x, (0, t.row0), (b, t.rows))
        wt = jax.lax.dynamic_slice(weight, (t.row0, t.col0), (t.rows, t.cols))
        yt = matmul_fn(xt, wt, t)
        y = jax.lax.dynamic_update_slice(
            y, jax.lax.dynamic_slice(y, (0, t.col0), (b, t.cols)) + yt,
            (0, t.col0))
    return y


def interleave_assignment(n_units: int, n_cores: int):
    """Paper Fig. 4f: assign adjacent pixels (visible units) to different cores
    so each core sees a down-sampled version of the whole image, equalizing
    per-core output dynamic range. Returns core index per unit."""
    return jnp.arange(n_units) % n_cores

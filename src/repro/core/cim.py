"""High-level CIM API — the chip-compiler pipeline models deploy through.

Chip deployment is an explicit five-stage compiler —

    plan  ->  schedule  ->  program  ->  calibrate  ->  pack

— where every stage is a standalone, testable function producing a typed
artifact (see DESIGN.md 'Chip-compiler pipeline'):

  * `plan_chip`       (mapping.plan_layers): matrices -> `Plan` of core tiles
                      (split / duplicate / merge, plus IR-drop-bounded
                      vertical splits via `mapping.ir_drop_max_cols`).
  * `schedule_chip`   (mapping.schedule_tiles): `Plan` -> per-layer
                      `TileSchedule` serializing same-core seq_slot tiles
                      into ordered passes (merged cores are time-shared).
  * `program_chip`    : weights -> `CIMLayer` conductances per matrix, at one
                      of three fidelities mirroring the paper's conditions —
                      'ideal' (exact encode), 'relaxed' (+relaxation noise,
                      3 iterations), 'writeverify' (full pulse-level sim).
  * `calibrate_chip`  : per-core ADC operating points — one v_decr per tile,
                      measured on that tile's own partial-sum distribution.
  * `pack_chip`       (mapping.pack_tiles): everything above folded into
                      per-layer `PackedCIMLayer` single-dispatch tensors.

`compile_chip` composes the five stages into a `CompiledChip` pytree — THE
serving artifact: `CIMEngine` wraps one for interactive use, and
`models/nn.deploy_packed_stack` stacks the layers of one across a scanned
transformer stack (one chip per transformer layer, one engine per TP shard).
The pipeline's cross-stage invariants (schedule a permutation of the plan,
packed index maps in bounds, fused runs consecutive, transpose packs
sharing the forward conductance stack) are NOT assumed to hold by
construction: `compile_chip(verify="strict")` — the default — runs the
chip-IR verifier (`core.verify.verify_chip`) over every emitted artifact
and raises a structured `ChipVerifyError` naming the stage, tile and
violated invariant before anything reaches a dispatch.

BIDIRECTIONAL execution (paper Fig. 4e-g; the TNSA runs MVMs SL->BL and
BL->SL over one programmed array): `compile_chip(...,
directions=("fwd", "bwd"))` keeps ONE conductance set per matrix and runs
the calibrate + pack stages PER DIRECTION — the transpose direction gets
its own per-tile v_decr measured on its own partial-sum distribution and a
packed view that shares the forward gd_tiles stack by reference
(`mapping.pack_tiles_transposed`, no conductance copy). `CIMEngine
.forward(name, x, direction="bwd")` then dispatches the transpose-direction
packed kernel; `models/nn.deploy_rbm_cim` builds the RBM Gibbs chip on it.

`program` / `forward` remain as thin COMPAT-ONLY single-matrix wrappers for
the per-layer oracle demos and tests: one full-matrix fused kernel (or the
bit-serial oracle when per-phase non-idealities are enabled), returning the
de-normalized digital output in x @ W units with measured ADC offsets
cancelled — exactly the chip's digital post-processing.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .types import CIMConfig, CoreSpec
from .quant import quantize_to_int
from .conductance import weights_to_conductances, program_conductances
from .calibration import (calibrate_layer, calibrate_v_decr,
                          tile_partial_sums, LayerCalibration)
from .writeverify import iterative_program
from .mapping import (MatrixReq, Plan, PackedPlan, TileSchedule,
                      ir_drop_max_cols, pack_tiles, pack_tiles_transposed,
                      plan_layers, schedule_tiles)
from .verify import ChipVerifyError, verify_chip
from ..kernels.cim_mvm.ops import cim_mvm, cim_mvm_packed
from ..kernels.cim_mvm.ref import cim_mvm_ref, dequantize_output


class CIMLayer(NamedTuple):
    """Pytree: one weight matrix programmed onto (simulated) RRAM cores."""
    g_pos: jax.Array
    g_neg: jax.Array
    w_max: jax.Array
    norm: jax.Array
    v_decr: jax.Array
    adc_offset: jax.Array
    in_alpha: jax.Array     # PACT input clip


def program(key, w, cfg: CIMConfig, in_alpha=1.0,
            x_cal: Optional[jax.Array] = None, mode: str = "relaxed"
            ) -> CIMLayer:
    """COMPAT-ONLY per-matrix wrapper: program weight matrix w (R, C) onto
    the chip and calibrate it.

    Deployment goes through `compile_chip` (the five-stage pipeline); this
    wrapper remains for the per-layer oracle/demo path only (per-phase
    non-idealities that need the bit-serial reference, models/nn.ChipLinear)
    and for tests of the programming stages. Do not add serving-path
    callers — tests/test_bidirectional.py audits for them.

    x_cal: optional (B_cal, R) float training-set activations for model-driven
    calibration; defaults to a synthetic batch matched to in_alpha (the paper
    shows training-set data is the right choice — tests quantify the gap).
    """
    k_prog, k_cal, k_syn = jax.random.split(key, 3)
    if mode == "ideal":
        c = weights_to_conductances(w, cfg.device)
    elif mode == "relaxed":
        c = program_conductances(k_prog, w, cfg.device, iterations=3)
    elif mode == "writeverify":
        ideal = weights_to_conductances(w, cfg.device)
        g_pos = iterative_program(k_prog, ideal.g_pos, cfg.device)
        g_neg = iterative_program(jax.random.fold_in(k_prog, 1), ideal.g_neg,
                                  cfg.device)
        norm = jnp.sum(g_pos + g_neg, axis=0)
        c = type(ideal)(g_pos, g_neg, ideal.w_max, norm)
    else:
        raise ValueError(mode)

    if x_cal is None:
        x_cal = in_alpha * jax.random.truncated_normal(
            k_syn, -2.0, 2.0, (64, w.shape[0]))
    x_int_cal, _ = quantize_to_int(x_cal, in_alpha, cfg.in_bits, signed=True)
    cal = calibrate_layer(k_cal, x_int_cal, c.g_pos, c.g_neg, cfg)
    return CIMLayer(c.g_pos, c.g_neg, c.w_max, c.norm, cal.v_decr,
                    cal.adc_offset, jnp.asarray(in_alpha, jnp.float32))


def forward(layer: CIMLayer, x, cfg: CIMConfig, *, key=None,
            use_kernel: bool = True, seed: int = 0):
    """COMPAT-ONLY per-matrix wrapper: y ~= x @ W through the chip
    datapath. x: (B, R) float.

    Serving runs through `CompiledChip` / `packed_forward`; this wrapper
    remains for the per-layer oracle/demo path (bit-serial per-phase
    non-idealities, models/nn.chip_linear) — see `program`.
    """
    x_int, scale = quantize_to_int(x, layer.in_alpha, cfg.in_bits, signed=True)
    if use_kernel and not _needs_ref(cfg):
        counts = cim_mvm(x_int, layer.g_pos, layer.g_neg, layer.v_decr, cfg,
                         seed=seed, norm=layer.norm)
    else:
        out = cim_mvm_ref(x_int, layer.g_pos, layer.g_neg, layer.v_decr, cfg,
                          key=key, adc_offset=layer.adc_offset,
                          bit_serial=_needs_ref(cfg))
        counts = out.counts
    # digital offset cancellation (offsets were measured during calibration)
    off_counts = jnp.round(layer.adc_offset / layer.v_decr)
    if cfg.activation == "none":
        counts = counts - off_counts[None, :]
    return dequantize_output(counts, layer.v_decr, layer.norm, layer.w_max,
                             scale, cfg)


def _needs_ref(cfg: CIMConfig) -> bool:
    """Per-phase non-idealities require the bit-serial oracle path."""
    ni = cfg.nonideal
    return (ni.ir_drop_alpha > 0 or ni.wire_r_alpha > 0
            or ni.coupling_sigma > 0 or ni.adc_offset_sigma > 0
            or cfg.activation == "stochastic")


def _oracle_only(cfg: CIMConfig) -> bool:
    """Non-idealities the packed serving path cannot honor at all.

    IR drop is deliberately NOT in this list: the planner MITIGATES it by
    bounding columns per core (`mapping.ir_drop_max_cols`), after which the
    residual droop is below the per-core ADC calibration tolerance — the
    paper's reason for splitting wide matrices vertically. The
    stochastic-neuron mode is not in it either: the packed kernels carry a
    deterministic hash-PRNG LFSR analogue, so comparator-bit sampling is
    servable (the RBM Gibbs loop). The remaining per-phase effects
    (crossbar wire IR, coupling, ADC offset spread) still need the
    bit-serial oracle.
    """
    ni = cfg.nonideal
    return (ni.wire_r_alpha > 0 or ni.coupling_sigma > 0
            or ni.adc_offset_sigma > 0)


def effective_weight(layer: CIMLayer, cfg: CIMConfig):
    """The weight the (noisy) array actually realizes."""
    return (layer.g_pos - layer.g_neg) * layer.w_max / cfg.device.g_max


# --------------------------------------------------------------- CIMEngine

class PackedCIMLayer(NamedTuple):
    """Pytree: one programmed layer + its packed tile plan (fold_norm=True,
    so the packed kernel's accumulation yields de-normalized charge units)."""
    layer: CIMLayer
    packed: PackedPlan


def calibrate_tile_v_decr(layer: CIMLayer, tiles, x_cal, cfg: CIMConfig,
                          coverage: float = 0.999, *,
                          direction: str = "fwd",
                          in_alpha: Optional[float] = None):
    """Per-core, per-DIRECTION ADC calibration: one v_decr per tile,
    covering that tile's OWN normalized partial-sum distribution in the
    requested access direction.

    The whole-matrix v_decr from calibrate_layer is wrong for split plans:
    a row-split tile's q_t = (x_t @ gd_t) * v_read / norm_t is distributed
    differently from the full matrix's q (fewer summed rows, its own
    normalizer) — the chip calibrates each core separately for exactly this
    reason. The transpose direction ('bwd') reads the SAME cells with the
    input/output wire roles swapped, so its distribution differs again
    (per-row normalizer, that direction's own activations); x_cal then
    lives in the direction's input space ((B, C) for 'bwd') and `in_alpha`
    overrides the forward clip stored on the layer.
    Returns (T,) aligned with the replica-0 tiles in given order.
    """
    alpha = layer.in_alpha if in_alpha is None else in_alpha
    x_int, _ = quantize_to_int(x_cal, alpha, cfg.in_bits, signed=True)
    tiles = [t for t in tiles if not t.replica]
    # one batched pass per tile extent (a handful per layer), not per tile
    by_extent: Dict[Tuple[int, int], list] = {}
    for i, t in enumerate(tiles):
        by_extent.setdefault((t.rows, t.cols), []).append(i)
    parts, where = [], []
    for idx in by_extent.values():
        q = tile_partial_sums(x_int, layer.g_pos, layer.g_neg,
                              [tiles[i] for i in idx], cfg, direction)
        parts.append(calibrate_v_decr(q, cfg, coverage, axis=(1, 2)))
        where += idx
    return jnp.concatenate(parts)[np.argsort(where)]


def pack_cim_layer(layer: CIMLayer, tiles, cfg: CIMConfig, v_decr=None,
                   schedule: Optional[TileSchedule] = None) -> PackedCIMLayer:
    """Pack a programmed CIMLayer's tiles for single-dispatch execution.

    Per-tile voltage-mode normalizers are computed from the tile's own rows
    (each tile is one physical core: norm_j = sum over that core's rows of
    G+ + G-), and norm * v_decr is folded into denorm_tiles. Activation
    modes whose counts are already neuron units (tanh/sigmoid/stochastic)
    keep raw count accumulation instead.

    v_decr: per-tile (T,) steps from calibrate_tile_v_decr; defaults to the
    layer's whole-matrix step (exact for single-tile plans, a systematic
    ADC range mismatch for split plans — prefer per-tile).
    schedule: optional `mapping.TileSchedule` over the same tiles (pass-major
    seq-slot serialization); None packs the single-pass tile-grid layout.
    """
    fold = cfg.activation not in ("tanh", "sigmoid", "stochastic")
    packed = pack_tiles(tiles, layer.g_pos - layer.g_neg,
                        gsum=layer.g_pos + layer.g_neg,
                        v_decr=layer.v_decr if v_decr is None else v_decr,
                        fold_norm=fold, schedule=schedule)
    return PackedCIMLayer(layer, packed)


def packed_forward(pcl: PackedCIMLayer, x, cfg: CIMConfig, *, seed=0,
                   interpret=None):
    """y ~= x @ W through the packed chip datapath — the functional core of
    CIMEngine.forward, safe to call inside an outer jit (models/serving).

    x: (B, R) float covering the layer's full weight-row space. The whole
    tile plan executes as one Pallas dispatch; row-split partial sums are
    de-normalized per core and accumulated digitally in the kernel.
    """
    layer, packed = pcl.layer, pcl.packed
    if cfg.activation == "stochastic" and packed.n_row_blocks > 1:
        raise ValueError(
            f"stochastic sampling on plan '{packed.layer}' would sum "
            f"comparator bits across {packed.n_row_blocks} input splits "
            "into non-Bernoulli values; serve a direction whose input fits "
            "one block (the raw executor multicore_mvm_packed keeps the "
            "summed-bit semantics for loop-parity studies)")
    x_int, scale = quantize_to_int(x, layer.in_alpha, cfg.in_bits,
                                   signed=True)
    acc = cim_mvm_packed(x_int, packed, cfg, seed=seed, interpret=interpret)
    if cfg.activation in ("tanh", "sigmoid", "stochastic"):
        return acc                     # already neuron units
    return acc * layer.w_max * scale / (cfg.v_read * cfg.device.g_max)


# ------------------------------------------------- chip-compiler pipeline

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(eq=False)
class CompiledChip:
    """The chip-compiler's output artifact: every stage's result, servable.

    Pytree: the packed per-layer tensors (`layers`) are children — so a
    CompiledChip can ride through jit/tree_map — while the config and the
    intermediate plan/schedule artifacts are aux data kept for
    introspection, tests and re-planning. jit hashes the treedef, so aux
    must be hashable: the schedules dict travels as a sorted items tuple
    (TileSchedule is frozen), and the Plan is identity-hashed.
    The chip is programmed ONCE; when compiled with
    directions=("fwd", "bwd") every matrix additionally carries a
    TRANSPOSE-DIRECTION packed view in `bwd_layers` — same gd_tiles stack
    (shared by reference, no conductance copy), per-direction calibration
    and normalizers — the TNSA's bidirectional (SL->BL and BL->SL) access.
    """
    cfg: CIMConfig
    spec: CoreSpec
    mode: str
    plan: Plan
    schedules: Dict[str, TileSchedule]
    layers: Dict[str, PackedCIMLayer]
    bwd_layers: Dict[str, PackedCIMLayer] = dataclasses.field(
        default_factory=dict)

    @property
    def directions(self) -> Tuple[str, ...]:
        return ("fwd", "bwd") if self.bwd_layers else ("fwd",)

    def layers_for(self, direction: str) -> Dict[str, PackedCIMLayer]:
        if direction == "fwd":
            return self.layers
        if direction == "bwd":
            if not self.bwd_layers:
                raise ValueError(
                    "chip was not compiled with directions=('fwd','bwd')")
            return self.bwd_layers
        raise ValueError(f"direction must be 'fwd' or 'bwd', got "
                         f"{direction!r}")

    def tree_flatten(self):
        return ((self.layers, self.bwd_layers),
                (self.cfg, self.spec, self.mode, self.plan,
                 tuple(sorted(self.schedules.items()))))

    @classmethod
    def tree_unflatten(cls, aux, children):
        cfg, spec, mode, plan, sched_items = aux
        return cls(cfg=cfg, spec=spec, mode=mode, plan=plan,
                   schedules=dict(sched_items), layers=children[0],
                   bwd_layers=children[1])

    def __contains__(self, name: str) -> bool:
        return name in self.layers


def plan_chip(reqs: Sequence[MatrixReq], cfg: CIMConfig,
              spec: CoreSpec = CoreSpec()) -> Plan:
    """Stage 1 (PLAN): allocate all matrices onto the chip's cores together
    (split / duplicate / merge, paper Fig. 2a), bounding tile width by the
    IR-drop constraint when `cfg.nonideal.ir_drop_alpha` > 0."""
    return plan_layers(reqs, spec,
                       max_cols_per_core=ir_drop_max_cols(cfg, spec))


def schedule_chip(plan: Plan, names: Sequence[str]
                  ) -> Dict[str, TileSchedule]:
    """Stage 2 (SCHEDULE): serialize each layer's same-core seq_slot tiles
    into ordered passes (merged cores are time-shared; distinct cores
    overlap within a pass)."""
    return {n: schedule_tiles(plan.tiles_for(n)) for n in names}


def program_chip(key, weights: Dict[str, jax.Array], cfg: CIMConfig, *,
                 mode: str = "relaxed",
                 in_alpha: Union[float, Dict[str, float]] = 1.0,
                 x_cal: Optional[Dict[str, jax.Array]] = None
                 ) -> Tuple[Dict[str, CIMLayer], Dict[str, jax.Array]]:
    """Stage 3 (PROGRAM): write every weight matrix into (simulated) RRAM
    conductances at the requested fidelity and run the whole-matrix
    calibration. Returns (name -> CIMLayer, name -> calibration batch) — the
    same batch must drive stage 4 so both calibrations see one activation
    distribution (paper: training-set data, Extended Data Fig. 5)."""
    layers: Dict[str, CIMLayer] = {}
    batches: Dict[str, jax.Array] = {}
    for i, name in enumerate(sorted(weights)):
        alpha = _alpha_for(in_alpha, name)
        k_layer, k_syn = jax.random.split(jax.random.fold_in(key, i))
        xc = x_cal.get(name) if x_cal is not None else None
        if xc is None:
            xc = alpha * jax.random.truncated_normal(
                k_syn, -2.0, 2.0, (64, weights[name].shape[0]))
        layers[name] = program(k_layer, weights[name], cfg,
                               in_alpha=alpha, x_cal=xc, mode=mode)
        batches[name] = xc
    return layers, batches


def _alpha_for(in_alpha: Union[float, Dict[str, float]], name: str) -> float:
    return (in_alpha.get(name, 1.0)
            if isinstance(in_alpha, dict) else in_alpha)


def calibrate_chip(layers: Dict[str, CIMLayer], plan: Plan,
                   batches: Dict[str, jax.Array], cfg: CIMConfig, *,
                   direction: str = "fwd",
                   in_alpha: Optional[Union[float, Dict[str, float]]] = None
                   ) -> Dict[str, jax.Array]:
    """Stage 4 (CALIBRATE): per-core ADC operating points — one v_decr per
    tile PER DIRECTION, covering that tile's own partial-sum distribution
    in that access direction (the chip calibrates each core separately, and
    the transpose direction sees a different distribution — per-row
    normalizer, its own activations). batches live in the direction's input
    space ((B, C) per name for 'bwd'); in_alpha overrides the forward clip
    for the transpose direction."""
    return {n: calibrate_tile_v_decr(
        layers[n], plan.tiles_for(n), batches[n], cfg, direction=direction,
        in_alpha=None if in_alpha is None else _alpha_for(in_alpha, n))
        for n in layers}


def pack_chip(layers: Dict[str, CIMLayer], plan: Plan,
              schedules: Dict[str, TileSchedule], cfg: CIMConfig,
              v_decrs: Dict[str, jax.Array], *, direction: str = "fwd",
              packed: Optional[Dict[str, PackedCIMLayer]] = None,
              in_alpha: Union[float, Dict[str, float]] = 1.0
              ) -> Dict[str, PackedCIMLayer]:
    """Stage 5 (PACK): fold conductances, normalizers and per-core ADC steps
    into each layer's scheduled single-dispatch tensors.

    direction='bwd' packs the TRANSPOSE-DIRECTION view of an already-packed
    forward chip (`packed` = the forward stage-5 output): the gd_tiles
    stacks are SHARED by reference — one programmed conductance set — and
    only the per-direction normalizer / denorm / ADC-step tensors are
    built (`mapping.pack_tiles_transposed`). v_decrs then comes from the
    'bwd' calibrate stage and in_alpha is the transpose direction's input
    clip (scalar or per-name).
    """
    if direction == "fwd":
        return {n: pack_cim_layer(layers[n], plan.tiles_for(n), cfg,
                                  v_decr=v_decrs[n], schedule=schedules[n])
                for n in layers}
    if direction != "bwd":
        raise ValueError(f"direction must be 'fwd' or 'bwd', got "
                         f"{direction!r}")
    if packed is None:
        raise ValueError("direction='bwd' needs the forward pack "
                         "(packed=...) whose gd_tiles it shares")
    fold = cfg.activation not in ("tanh", "sigmoid", "stochastic")
    out: Dict[str, PackedCIMLayer] = {}
    for n, lay in layers.items():
        p_bwd = pack_tiles_transposed(
            plan.tiles_for(n), packed[n].packed,
            gsum=lay.g_pos + lay.g_neg, v_decr=v_decrs[n],
            fold_norm=fold, schedule=schedules[n])
        # the transpose-direction CIMLayer view: SAME conductance arrays
        # (by reference), with that direction's normalizer (per-row sums),
        # a conservative whole-matrix ADC step (the per-tile steps in the
        # pack are what serve) and its own input clip
        lay_bwd = CIMLayer(
            lay.g_pos, lay.g_neg, lay.w_max,
            jnp.sum(lay.g_pos + lay.g_neg, axis=1),
            jnp.max(v_decrs[n]),
            jnp.zeros((lay.g_pos.shape[0],), jnp.float32),
            jnp.asarray(_alpha_for(in_alpha, n), jnp.float32))
        out[n] = PackedCIMLayer(lay_bwd, p_bwd)
    return out


def compile_chip(key, weights: Dict[str, jax.Array], cfg: CIMConfig,
                 spec: CoreSpec = CoreSpec(), mode: str = "relaxed", *,
                 reqs: Optional[Sequence[MatrixReq]] = None,
                 plan: Optional[Plan] = None,
                 in_alpha: Union[float, Dict[str, float]] = 1.0,
                 x_cal: Optional[Dict[str, jax.Array]] = None,
                 directions: Sequence[str] = ("fwd",),
                 in_alpha_bwd: Union[float, Dict[str, float]] = 1.0,
                 x_cal_bwd: Optional[Dict[str, jax.Array]] = None,
                 verify: str = "strict") -> CompiledChip:
    """Run the full pipeline: plan -> schedule -> program -> calibrate ->
    pack one chip's worth of weight matrices into a servable CompiledChip.

    weights: name -> (R, C) float weight matrix.
    reqs: optional MatrixReqs (intensities steer duplication); defaults to
    one plain req per weight. in_alpha: PACT clip, scalar or per-name.
    x_cal: optional per-name (B_cal, R) calibration activations.
    plan: optional pre-built Plan overriding stage 1 (custom mappings such
    as the pixel-interleaved RBM assignment — the caller then owns the
    IR-drop constraint that plan_chip would have applied).
    directions: ("fwd",) or ("fwd", "bwd"). With "bwd", every matrix is
    ALSO calibrated and packed in the transpose (BL->SL) direction —
    stages 4 and 5 run per direction on the direction's own partial-sum
    distribution, while the programmed conductances (stage 3) and the
    shared gd_tiles stacks stay single-copy. in_alpha_bwd / x_cal_bwd are
    the transpose direction's input clip and (B_cal, C) calibration
    activations (synthetic fallback matched to the clip, like forward).
    verify: "strict" (default) runs the chip-IR verifier
    (`core.verify.verify_chip`) over every stage artifact before the chip
    is returned — a violated invariant raises `ChipVerifyError` naming
    stage, layer, tile and invariant instead of dispatching a corrupt
    layout. "off" skips verification (a caller that just verified, or a
    deliberately degenerate test artifact).
    """
    if verify not in ("strict", "off"):
        raise ValueError(f"verify must be 'strict' or 'off', got "
                         f"{verify!r}")
    if _oracle_only(cfg):
        raise ValueError(
            "compile_chip serves the fused kernel path only; per-phase "
            "non-idealities require the bit-serial oracle (core.forward)")
    directions = tuple(directions)
    if "fwd" not in directions or set(directions) - {"fwd", "bwd"}:
        raise ValueError(f"directions must be ('fwd',) or ('fwd','bwd'), "
                         f"got {directions}")
    if plan is None:
        reqs = list(reqs) if reqs is not None else [
            MatrixReq(n, int(w.shape[0]), int(w.shape[1]))
            for n, w in weights.items()]
        if {r.name for r in reqs} != set(weights):
            raise ValueError("reqs names must match weights names")
        plan = plan_chip(reqs, cfg, spec)
    else:
        for n, w in weights.items():
            ts = plan.tiles_for(n)
            if not ts:
                raise ValueError(f"supplied plan has no tiles for '{n}'")
            ext = (max(t.row0 + t.rows for t in ts),
                   max(t.col0 + t.cols for t in ts))
            if ext != tuple(w.shape):
                raise ValueError(
                    f"supplied plan covers {ext} for '{n}' but the weight "
                    f"is {tuple(w.shape)}")
    schedules = schedule_chip(plan, sorted(weights))
    layers, batches = program_chip(key, weights, cfg, mode=mode,
                                   in_alpha=in_alpha, x_cal=x_cal)
    v_decrs = calibrate_chip(layers, plan, batches, cfg)
    packed = pack_chip(layers, plan, schedules, cfg, v_decrs)
    bwd_packed: Dict[str, PackedCIMLayer] = {}
    if "bwd" in directions:
        batches_bwd: Dict[str, jax.Array] = {}
        for i, n in enumerate(sorted(weights)):
            xc = x_cal_bwd.get(n) if x_cal_bwd is not None else None
            if xc is None:
                alpha_b = _alpha_for(in_alpha_bwd, n)
                xc = alpha_b * jax.random.truncated_normal(
                    jax.random.fold_in(key, 1009 + i), -2.0, 2.0,
                    (64, weights[n].shape[1]))
            batches_bwd[n] = xc
        v_decrs_bwd = calibrate_chip(layers, plan, batches_bwd, cfg,
                                     direction="bwd", in_alpha=in_alpha_bwd)
        bwd_packed = pack_chip(layers, plan, schedules, cfg, v_decrs_bwd,
                               direction="bwd", packed=packed,
                               in_alpha=in_alpha_bwd)
    chip = CompiledChip(cfg=cfg, spec=spec, mode=mode, plan=plan,
                        schedules=schedules, layers=packed,
                        bwd_layers=bwd_packed)
    if verify == "strict":
        verify_chip(chip)
    return chip


class CIMEngine:
    """Serves a CompiledChip: compile once, then batched forward requests run
    through one jit'd dispatch per layer.

    Usage:
        eng = CIMEngine(cfg, mode="relaxed")
        eng.program(key, {"fc1": w1, "fc2": w2})      # the 5-stage pipeline
        y = eng.forward("fc1", x)                     # single pallas_call

    The compiler allocates all matrices onto the chip's cores together
    (split / duplicate / merge / IR-drop splits, paper Fig. 2a) and
    serializes merged cores into passes; each layer then executes as ONE
    packed Pallas dispatch — a single jit trace per plan shape, so the
    engine drops into a serving loop without per-tile retracing.

    Per-phase non-idealities other than IR drop (crossbar wire IR, coupling,
    ADC offset spread) need the bit-serial oracle and are not servable from
    the packed path; such configs raise — use the per-layer `forward` demo
    path instead. IR drop IS servable: the planner bounds columns per core
    so the droop stays within calibration tolerance.

    device: optional jax.Device (or Sharding) the compiled chip is placed
    on at PROGRAM time — the single-chip analogue of the mesh-resident TP
    deploy (models/nn.deploy_transformer_cim(mesh=...)): chip state lives
    where it executes, and per-request forwards never move conductances.
    None keeps jax's default placement.
    """

    def __init__(self, cfg: CIMConfig, spec: CoreSpec = CoreSpec(),
                 mode: str = "relaxed", interpret: Optional[bool] = None,
                 device=None):
        if _oracle_only(cfg):
            raise ValueError(
                "CIMEngine serves the fused kernel path only; per-phase "
                "non-idealities require the bit-serial oracle (core.forward)")
        self.cfg = cfg
        self.spec = spec
        self.mode = mode
        self.interpret = interpret
        self.device = device
        self.chip: Optional[CompiledChip] = None
        # seed is a traced SMEM input, so per-call seeds never retrace
        # (matters for stochastic-activation sampling, where every Gibbs
        # half-step threads a fresh seed)
        self._dispatch = jax.jit(
            functools.partial(packed_forward, cfg=cfg, interpret=interpret))

    @property
    def plan(self) -> Optional[Plan]:
        return self.chip.plan if self.chip is not None else None

    @property
    def layers(self) -> Dict[str, PackedCIMLayer]:
        return self.chip.layers if self.chip is not None else {}

    def program(self, key, weights: Dict[str, jax.Array], *,
                reqs: Optional[Sequence[MatrixReq]] = None,
                plan: Optional[Plan] = None,
                in_alpha: Union[float, Dict[str, float]] = 1.0,
                x_cal: Optional[Dict[str, jax.Array]] = None,
                directions: Sequence[str] = ("fwd",),
                in_alpha_bwd: Union[float, Dict[str, float]] = 1.0,
                x_cal_bwd: Optional[Dict[str, jax.Array]] = None) -> Plan:
        """Compile `weights` into a fresh CompiledChip (re-programming
        discards the old chip state). See `compile_chip`; with
        directions=("fwd", "bwd") every matrix also serves transposed.
        With `device` set on the engine, the chip is device_put there
        once, here — deploy-time placement, not per-call transfer."""
        self.chip = compile_chip(key, weights, self.cfg, self.spec,
                                 self.mode, reqs=reqs, plan=plan,
                                 in_alpha=in_alpha, x_cal=x_cal,
                                 directions=directions,
                                 in_alpha_bwd=in_alpha_bwd,
                                 x_cal_bwd=x_cal_bwd)
        if self.device is not None:
            self.chip = jax.device_put(self.chip, self.device)
        return self.chip.plan

    def forward(self, name: str, x, *, direction: str = "fwd",
                seed: int = 0):
        """y ~= x @ W_name (direction='fwd', SL->BL) or x @ W_name.T
        (direction='bwd', BL->SL — the transpose-direction packed dispatch
        over the same programmed cells) via one pallas_call."""
        return self._dispatch(self.chip.layers_for(direction)[name], x,
                              seed=jnp.asarray(seed, jnp.int32))

    def __contains__(self, name: str) -> bool:
        return name in self.layers

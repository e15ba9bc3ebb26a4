"""Neural-network substrate over the CIM core.

Every weight matrix has two execution paths:

  * TRAIN path (float, differentiable): PACT-quantized activations (STE) and
    per-step Gaussian weight-noise injection — the paper's noise-resilient
    training (Fig. 3c). Runs the noisy_matmul Pallas kernel when jitted on
    TPU; plain jnp here.
  * CHIP path (inference, integer): the weight (with bias and folded batch-norm
    merged in, paper Fig. 4c) is programmed onto simulated RRAM with the
    bias-as-rows scheme, calibrated, and executed through the CIM datapath.

Bias-as-rows (paper Methods): if the bias range is B times the weight range,
the bias is split evenly over B appended rows driven with full-scale inputs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.types import CIMConfig, CoreSpec, NonIdealityConfig
from ..core.quant import pact_quantize
from ..core.noise import weight_noise
from ..core import cim as cim_api
from ..core.mapping import slice_tile_stacks, take
from ..core.verify import verify_deployed


# ---------------------------------------------------------------- init utils

def linear_init(key, n_in, n_out):
    kw, _ = jax.random.split(key)
    w = jax.random.normal(kw, (n_in, n_out)) * math.sqrt(2.0 / n_in)
    return {"w": w, "b": jnp.zeros((n_out,))}


def conv_init(key, kh, kw_, cin, cout):
    k, _ = jax.random.split(key)
    fan_in = kh * kw_ * cin
    w = jax.random.normal(k, (kh, kw_, cin, cout)) * math.sqrt(2.0 / fan_in)
    return {"w": w, "b": jnp.zeros((cout,))}


def bn_init(c):
    return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,)),
            "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}


# ----------------------------------------------------------- train-time path

def quant_act(x, alpha, bits: int, signed: bool):
    """PACT activation quantization with STE; identity if bits <= 0."""
    if bits <= 0:
        return x
    return pact_quantize(x, alpha, bits, signed=signed)


def noisy_linear(key, p, x, noise_frac: float):
    w = p["w"]
    if noise_frac > 0.0 and key is not None:
        w = weight_noise(key, w, noise_frac)
    return x @ w + p["b"]


def im2col(x, kh, kw_, stride=1, padding="SAME"):
    """x: (B,H,W,C) -> patches (B, Ho, Wo, kh*kw*C)."""
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw_), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return patches  # channel-last: kh*kw*C


def noisy_conv(key, p, x, noise_frac: float, stride=1, padding="SAME"):
    kh, kw_, cin, cout = p["w"].shape
    cols = im2col(x, kh, kw_, stride, padding)           # (B,Ho,Wo,kh*kw*cin)
    w2 = p["w"].reshape(kh * kw_ * cin, cout)
    if noise_frac > 0.0 and key is not None:
        w2 = weight_noise(key, w2, noise_frac)
    return cols @ w2 + p["b"]


def batch_norm(p, x, train: bool, momentum=0.9, eps=1e-5):
    """Returns (y, updated_bn_params). Reduction over all but last axis."""
    if train:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x, axes)
        var = jnp.var(x, axes)
        new_p = dict(p, mean=momentum * p["mean"] + (1 - momentum) * mean,
                     var=momentum * p["var"] + (1 - momentum) * var)
    else:
        mean, var, new_p = p["mean"], p["var"], p
    y = (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]
    return y, new_p


def fold_bn(conv_p, bn_p, eps=1e-5):
    """Merge BN into conv weights/bias (paper Fig. 4c) for chip deployment."""
    scale = bn_p["gamma"] / jnp.sqrt(bn_p["var"] + eps)
    w = conv_p["w"] * scale              # broadcast over output channel
    b = (conv_p["b"] - bn_p["mean"]) * scale + bn_p["beta"]
    return {"w": w, "b": b}


def max_pool(x, window=2, stride=2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def avg_pool_global(x):
    return jnp.mean(x, axis=(1, 2))


# ------------------------------------------------------------- chip-sim path

class ChipLinear(NamedTuple):
    """A linear/conv (flattened) layer programmed on the simulated chip."""
    layer: Any            # core.cim.CIMLayer
    bias_rows: int        # rows appended for the bias
    alpha: jax.Array      # input PACT clip used at deploy time
    signed: bool


def _augment_bias(w2, b, drive):
    """Append bias rows: bias split over B rows driven at full-scale input.

    `drive` is the constant input level the appended rows are fed at run
    time — the SIGNED full-scale input, i.e. the PACT clip alpha
    (`chip_linear` drives the rows at `cl.alpha` whether the data inputs
    are signed or unsigned; signed inputs top out at +alpha, unsigned ones
    never exceed it). Each row's conductance stays within the weight range
    because n_rows scales with bmax / (drive * wmax)."""
    wmax = jnp.maximum(jnp.max(jnp.abs(w2)), 1e-12)
    bmax = jnp.max(jnp.abs(b))
    n_rows = int(jnp.maximum(1, jnp.ceil(bmax / (drive * wmax))))
    rows = jnp.tile((b / (n_rows * drive))[None, :], (n_rows, 1))
    return jnp.concatenate([w2, rows], axis=0), n_rows


def deploy_linear(key, p, cfg: CIMConfig, alpha, x_cal=None,
                  signed: bool = False, mode: str = "relaxed") -> ChipLinear:
    """Program one weight matrix (+bias rows) onto simulated RRAM."""
    w2 = p["w"] if p["w"].ndim == 2 else p["w"].reshape(-1, p["w"].shape[-1])
    alpha = jnp.asarray(alpha, jnp.float32)
    w_aug, n_rows = _augment_bias(w2, p["b"], alpha)
    if x_cal is not None:
        ones = jnp.full((x_cal.shape[0], n_rows), alpha)
        x_cal = jnp.concatenate([x_cal.reshape(x_cal.shape[0], -1), ones], -1)
    layer = cim_api.program(key, w_aug, cfg, in_alpha=float(alpha),
                            x_cal=x_cal, mode=mode)
    return ChipLinear(layer, n_rows, alpha, signed)


def chip_linear(cl: ChipLinear, x, cfg: CIMConfig, key=None, seed: int = 0):
    """x: (B, n_in) float -> (B, n_out) float through the chip datapath."""
    ones = jnp.full((x.shape[0], cl.bias_rows), cl.alpha)
    x_aug = jnp.concatenate([x, ones], axis=-1)
    return cim_api.forward(cl.layer, x_aug, cfg, key=key, seed=seed)


def chip_conv(cl: ChipLinear, x, cfg: CIMConfig, kh, kw_, stride=1,
              padding="SAME", key=None, seed: int = 0):
    cols = im2col(x, kh, kw_, stride, padding)
    b, ho, wo, d = cols.shape
    y = chip_linear(cl, cols.reshape(-1, d), cfg, key=key, seed=seed)
    return y.reshape(b, ho, wo, -1)


# --------------------------------------------- packed CIM serving (engine)

# Projection matrices the packed serving path covers: dense-block + shared-
# expert projections (2-D per layer), routed-expert stacks (3-D per layer,
# one chip per expert), and the recurrent stacks — rwkv6 time-mix/channel-mix
# and mamba2 in/out + hybrid-MLP projections compile through
# `deploy_recurrent_cim` (one chip per layer; the S/h state recurrences
# themselves stay digital float — see DESIGN.md 'Serving surfaces').
PACKED_PROJ_KEYS = ("wq", "wk", "wv", "wo", "w_g", "w_i", "w_o",
                    "sw_g", "sw_i", "sw_o")
PACKED_EXPERT_KEYS = ("ew_g", "ew_i", "ew_o")
# rwkv6: time-mix r/k/v/g/out projections + channel-mix k/v/receptance
RWKV_PROJ_KEYS = ("wr", "wk", "wv", "wg", "wo", "ck", "cv", "cr")
# mamba2: fused in/out projections + the hybrid block's SwiGLU MLP
MAMBA_PROJ_KEYS = ("in_proj", "out_proj", "w_g", "w_i", "w_o")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedPackedLayer:
    """One projection's per-TP-shard packed engines, plus how to combine
    their outputs: Megatron-style column-parallel shards each produce a
    slice of the output (concatenate = the all-gather over 'model'),
    row-parallel shards each consume a slice of the input and produce
    partial sums (add = the psum over 'model'). `shards` is a
    PackedCIMLayer pytree whose arrays carry a leading shard dim (further
    leading dims appear when layer stacks are scanned). Two executors
    serve it: `sharded_packed_forward` runs each shard device-resident
    under shard_map on a real mesh (deploy-time placement maps the shard
    dim onto 'model'); `sharded_packed_loop` unrolls the shards in one
    process — the no-mesh executor and the parity oracle."""
    shards: Any            # PackedCIMLayer, leading (n_shards,) on arrays
    partition: str         # 'col' | 'row' | 'none'
    n_shards: int

    def tree_flatten(self):
        return (self.shards,), (self.partition, self.n_shards)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def sharded_packed_loop(spl: ShardedPackedLayer, x, ccfg: CIMConfig, *,
                        seed: int = 0):
    """Unrolled-loop executor for a ShardedPackedLayer — the NO-MESH
    executor (`serve --cim-mesh off`) and the PARITY ORACLE for the
    shard_map path.

    x: (B, R_global) float. Every shard's packed Pallas dispatch runs in
    one process, unrolled inside the serving jit (identical per-shard plan
    shapes share one kernel trace): 'row' shards read their input slice
    and their partial outputs fold left-to-right in shard order — the
    in-process analogue of the psum over 'model' — while 'col' shard
    outputs concatenate in shard order. A shard of a scanned layer stack
    reads its tiles in place (`core.mapping.take` keeps the stack whole).
    `sharded_packed_forward` is bitwise-equal to this loop on a real mesh
    (tests/test_mesh_serving.py holds the contract), so single-device
    serving and mesh serving cannot drift.
    """
    outs = []
    for s in range(spl.n_shards):
        pcl = take(spl.shards, s)
        xs = x
        if spl.partition == "row":
            r = x.shape[-1] // spl.n_shards
            xs = jax.lax.slice_in_dim(x, s * r, (s + 1) * r, axis=-1)
        outs.append(cim_api.packed_forward(pcl, xs, ccfg, seed=seed))
    if spl.n_shards == 1:
        return outs[0]
    if spl.partition == "row":
        return _ordered_fold(jnp.stack(outs))        # psum over 'model'
    return jnp.concatenate(outs, axis=-1)            # all-gather over 'model'


def _ordered_fold(parts):
    """Left-fold partial sums in shard order, one f32 add at a time, with
    the partials MATERIALIZED first — the one reduction both TP executors
    share, so they agree bitwise.

    The fold runs as a `lax.scan` deliberately: the while-loop boundary
    forces every partial to be a real buffer before any add. A plain
    unrolled `reduce(add, outs)` lets XLA CPU fuse each shard's final
    de-normalizing multiply (packed_forward's `acc * w_max * scale / ...`)
    into the neighboring add and contract the pair into an FMA — skipping
    the intermediate rounding and drifting 1 ulp from the device-resident
    mesh path, whose partials are materialized by the all-gather
    collective. (`lax.optimization_barrier` does NOT stop that
    contraction — it happens at LLVM level inside a fusion.) Identical
    adds on identical materialized values in identical order is the whole
    bitwise contract between `sharded_packed_loop` and
    `sharded_packed_forward`; change both or neither."""
    y, _ = jax.lax.scan(lambda c, p: (c + p, None), parts[0], parts[1:])
    return y


def sharded_packed_forward(spl: ShardedPackedLayer, x, ccfg: CIMConfig, *,
                           seed: int = 0, mesh=None,
                           row_reduce: str = "ordered"):
    """Serve one projection through its per-TP-shard engines.

    x: (B, R_global) float. With a real `mesh` (launch/mesh.serving_mesh)
    whose 'model' axis matches `spl.n_shards`, each shard's packed Pallas
    dispatch runs DEVICE-RESIDENT under `jax.shard_map`: the device
    holding shard s (its chip stack was placed there at deploy time —
    `deploy_transformer_cim(mesh=...)` via
    `distributed/sharding.packed_shardings`) executes that shard's plan
    locally, and the shards meet in exactly ONE collective per projection
    — the psum over 'model' for row-parallel partial sums, the out-spec
    all-gather for column-parallel output slices. This is the NeuRRAM
    dataflow at mesh scale: one compiled chip per parallel core (TP
    shard), partial sums reduced digitally between cores.

    row_reduce picks how the row-parallel psum lowers:
      * 'ordered' (default): all_gather + the shared `_ordered_fold`
        (left-fold add in shard order over materialized partials) —
        bitwise-equal to `sharded_packed_loop`: both sides reduce in
        the same deterministic shard order, whereas `lax.psum`'s
        reduction order is backend-defined and drifts by 1 ulp on
        split plans (the folded denorm makes shard partials
        non-integer floats, so addition order matters). The parity
        tests pin this contract at runtime, and the chip-IR verifier
        (`core.verify`, run by every deploy_*_cim path) statically
        checks the packed-layout invariants the equality rests on.
      * 'psum': `lax.psum` — fewer bytes on real interconnects (a ring
        all-reduce moves ~2x the output instead of n_shards x); use it
        when 1-ulp nondeterminism vs the single-device oracle is
        acceptable.

    Under shard_map each device gets its shard's own tiles: a plan that
    reads a scanned stack in place is first cut to its layer's slice
    (`core.mapping.slice_tile_stacks`), a copy of the layer's tiles that
    the one-process loop does not make.

    Without a mesh (`serve --cim-mesh off`, the parity oracle) execution
    takes `sharded_packed_loop`, the single-device executor the shard_map
    path is bitwise-tested against. Replicated projections (n_shards == 1)
    always take it too (one dispatch, replicated over the mesh by GSPMD).
    A multi-shard stack meeting a mesh whose 'model' width differs from its
    shard count raises: it was deployed for another mesh, and looping over
    its shards on one device would hide that.
    """
    if mesh is None or spl.n_shards == 1:
        return sharded_packed_loop(spl, x, ccfg, seed=seed)
    width = dict(mesh.shape).get("model", 1)
    if width != spl.n_shards:
        raise ValueError(
            f"projection deployed over {spl.n_shards} 'model' shards meets "
            f"a mesh whose 'model' axis has {width} devices: deploy for "
            "this mesh, or serve without one (--cim-mesh off)")
    part = spl.partition

    def shard_fn(shards, xs):
        pcl = jax.tree_util.tree_map(lambda a: jnp.squeeze(a, 0), shards)
        y = cim_api.packed_forward(pcl, xs, ccfg, seed=seed)
        if part == "row":                    # THE one collective
            if row_reduce == "psum":
                y = jax.lax.psum(y, "model")
            else:
                # all_gather materializes every shard's partial, then the
                # SAME fold as the loop oracle runs on every device
                y = _ordered_fold(jax.lax.all_gather(y, "model"))
        return y

    x_spec = P(None, "model") if part == "row" else P()
    out_spec = P(None, "model") if part == "col" else P()
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P("model"), x_spec), out_specs=out_spec,
                       check_vma=False)
    return fn(slice_tile_stacks(spl.shards), x)


def deploy_packed_stack(key, stacked_w: Dict[str, jax.Array],
                        ccfg: CIMConfig, *, mode: str = "ideal",
                        in_alpha: Union[float, Dict[str, float]] = 3.0,
                        spec: Optional[CoreSpec] = None) -> Dict[str, Any]:
    """Compile a scanned layer stack's weight matrices into packed chips.

    stacked_w: name -> (L, R, C) stacked weights (one scan step per layer),
    already sliced to the local TP shard if sharded (deploy_transformer_cim
    does this via distributed/sharding.shard_slice).
    in_alpha: PACT input clip — scalar, or per-name dict for stacks whose
    projections see differently-scaled activations (e.g. rwkv6's `cv`,
    driven by a squared-relu, rides a wider clip than the rms-normed mixes).
    A dict's keys must all name projections in this stack: an unknown key
    raises instead of silently deploying the projection it was meant to
    retune at the 1.0 default (`core.cim._alpha_for`'s fallback).
    Each layer index gets its own `core.cim.compile_chip` run (one chip per
    transformer layer): all of that layer's matrices go through the full
    plan -> schedule -> program -> calibrate -> pack pipeline ONCE. The
    resulting per-layer PackedCIMLayer pytrees are stacked back over L —
    their static plan geometry is pytree aux data, so the layer scan
    (`transformer.scan_layers`) runs them without retracing, every
    projection a single Pallas dispatch per step that reads its layer's
    tiles in place from the stack.
    """
    names = sorted(stacked_w)
    if isinstance(in_alpha, dict):
        unknown = sorted(set(in_alpha) - set(names))
        if unknown:
            raise ValueError(
                f"in_alpha names {unknown} match no projection in this "
                f"stack (stack names: {names}) — a typo here would "
                "silently deploy the projection at the default clip")
    n_layers = stacked_w[names[0]].shape[0]
    spec = spec or CoreSpec()

    per_layer = []
    for li in range(n_layers):
        chip = cim_api.compile_chip(
            jax.random.fold_in(key, li),
            {n: stacked_w[n][li].astype(jnp.float32) for n in names},
            ccfg, spec, mode, in_alpha=in_alpha)
        per_layer.append(chip.layers)
    return {n: _stack([pl.pop(n) for pl in per_layer]) for n in names}


def _stack(trees, axis: int = 0):
    """Stack same-structure pytrees leaf by leaf. Callers pop the inputs
    out of their containers first, so each projection's unstacked arrays
    are freed as soon as its stack exists: at published widths a layer's
    chip state is gigabytes, and holding every projection twice at once
    would not fit one accelerator."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=axis),
                                  *trees)


def packed_linear(pcl, x, ccfg: CIMConfig, *, seed: int = 0, mesh=None):
    """x: (B, n_in) float -> (B, n_out) float through one packed dispatch
    (or one per shard). pcl: one layer's core.cim.PackedCIMLayer or
    ShardedPackedLayer (its plans may read a layer stack in place,
    `scan_layers`). mesh: optional serving Mesh — multi-shard layers
    then execute device-resident under shard_map (sharded_packed_forward);
    None keeps the unrolled single-process loop."""
    if isinstance(pcl, ShardedPackedLayer):
        return sharded_packed_forward(pcl, x.astype(jnp.float32), ccfg,
                                      seed=seed, mesh=mesh)
    return cim_api.packed_forward(pcl, x.astype(jnp.float32), ccfg,
                                  seed=seed)


def arch_cim_config(arch_cfg, ccfg: Optional[CIMConfig] = None) -> CIMConfig:
    """The CIMConfig a transformer arch serves its packed projections with.

    ArchConfig.cim_in_bits/cim_out_bits/cim_ir_drop are the ONE source of
    truth for the chip operating point — deploy and the in-jit forward both
    derive their CIMConfig here so they cannot drift. A caller holding its
    own CIMConfig (chip-in-the-loop experiments) may pass it as `ccfg`; it
    is returned as-is ONLY if its precision/IR-drop fields agree with the
    arch — a mismatch raises instead of silently serving at a precision the
    forward pass does not expect.
    """
    ir_drop = getattr(arch_cfg, "cim_ir_drop", 0.0)
    if ccfg is not None:
        if (ccfg.in_bits != arch_cfg.cim_in_bits
                or ccfg.out_bits != arch_cfg.cim_out_bits
                or ccfg.nonideal.ir_drop_alpha != ir_drop):
            raise ValueError(
                "CIMConfig conflicts with the arch's CIM operating point: "
                f"in_bits {ccfg.in_bits} vs {arch_cfg.cim_in_bits}, "
                f"out_bits {ccfg.out_bits} vs {arch_cfg.cim_out_bits}, "
                f"ir_drop {ccfg.nonideal.ir_drop_alpha} vs {ir_drop} — "
                "set the arch's cim_* fields (serve.py --cim-bits) instead "
                "of passing a divergent config")
        return ccfg
    return CIMConfig(
        in_bits=arch_cfg.cim_in_bits, out_bits=arch_cfg.cim_out_bits,
        nonideal=NonIdealityConfig(ir_drop_alpha=ir_drop))


def _group_alpha(in_alpha, names):
    """Restrict a per-name in_alpha dict to one deploy group's names (the
    full dict is validated against the full stack up front; each
    deploy_packed_stack call re-validates against its own group)."""
    if not isinstance(in_alpha, dict):
        return in_alpha
    return {n: a for n, a in in_alpha.items() if n in names}


def place_packed_stack(tree, mesh, n_shards: int, shard_axis: int = 0):
    """Place a packed chip stack's arrays onto the serving mesh at DEPLOY
    time: the shard axis lands on 'model' (each device holds its own
    shard's compiled chips — distributed/sharding.packed_shardings), all
    other dims replicate. ShardedPackedLayers re-wrap with their aux
    preserved; raw trees (MoE expert stacks) place as-is. The shard_map
    serving path then runs with zero per-call transfers."""
    from ..distributed.sharding import packed_shardings
    arrs = tree.shards if isinstance(tree, ShardedPackedLayer) else tree
    placed = jax.tree_util.tree_map(
        jax.device_put, arrs,
        packed_shardings(mesh, arrs, n_shards, shard_axis))
    if isinstance(tree, ShardedPackedLayer):
        return ShardedPackedLayer(placed, tree.partition, tree.n_shards)
    return placed


def place_replicated(tree, mesh):
    """Replicate over `mesh` every array leaf not already placed on it —
    the float parameters a deploy leaves behind (embeddings, norms,
    attention biases). Done once at deploy time: a leaf left on one device
    would be copied to every mesh device on each serving call."""
    rep = NamedSharding(mesh, P())

    def put(a):
        sh = getattr(a, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh == mesh \
                or sh is not None and sh.is_equivalent_to(rep, a.ndim):
            return a                    # placed already: never a copy
        return jax.device_put(a, rep)
    return jax.tree_util.tree_map(put, tree)


def _deploy_sharded_stacks(key, stacked: Dict[str, jax.Array],
                           ccfg: CIMConfig, *, mode: str,
                           in_alpha: Union[float, Dict[str, float]],
                           mesh_shape: Dict[str, int],
                           spec: Optional[CoreSpec],
                           mesh=None
                           ) -> Dict[str, "ShardedPackedLayer"]:
    """Compile (L, R, C) weight stacks into per-TP-shard packed chip stacks.

    The shared deploy core of `deploy_transformer_cim` and
    `deploy_recurrent_cim`: ONE ENGINE PER 'model'-axis SHARD, each compiled
    from that shard's local slice of every projection
    (distributed/sharding.param_pspecs + shard_slice — a NeuRRAM 'core' is
    an intra-shard unit). Returns name -> ShardedPackedLayer whose arrays
    carry leading (L, n_shards) dims, ready for lax.scan over layers.
    With `mesh`, each multi-shard stack is additionally PLACED on the mesh
    (shard dim -> 'model', `place_packed_stack`) so the shard_map serving
    path finds every shard's chips already device-resident.

    Projections whose sharded dim is not divisible by the axis size fall
    back to a single replicated engine (fit_pspecs rule). Replicated
    ('none') projections compile on their OWN chip stack: mixing them into
    shard 0's chip would make the co-allocation planner produce shard-0
    plans that diverge from the other shards' (different merges/schedules),
    breaking the cross-shard stack.
    """
    from ..distributed.sharding import (param_pspecs, partition_kind,
                                        shard_slice, shard_shape)
    if isinstance(in_alpha, dict):
        unknown = sorted(set(in_alpha) - set(stacked))
        if unknown:
            raise ValueError(
                f"in_alpha names {unknown} match no projection in this "
                f"deploy (projections: {sorted(stacked)})")
    n_sh = max(int(mesh_shape.get("model", 1)), 1)
    specs = param_pspecs({"layers": dict(stacked)})["layers"]
    kinds = {}
    for n, w in stacked.items():
        try:
            shard_shape(w.shape, specs[n], {"model": n_sh})
            kinds[n] = partition_kind(specs[n]) if n_sh > 1 else "none"
        except ValueError:      # not divisible: replicate (fit_pspecs rule)
            kinds[n] = "none"

    sharded_names = sorted(n for n in stacked if kinds[n] != "none")
    none_names = sorted(n for n in stacked if kinds[n] == "none")
    shard_layers = []
    if sharded_names:
        for s in range(n_sh):
            local = {n: shard_slice(stacked[n], specs[n], {"model": n_sh},
                                    {"model": s}) for n in sharded_names}
            shard_layers.append(deploy_packed_stack(
                jax.random.fold_in(key, s), local, ccfg, mode=mode,
                in_alpha=_group_alpha(in_alpha, sharded_names), spec=spec))
    none_layers = {}
    if none_names:
        none_layers = deploy_packed_stack(
            jax.random.fold_in(key, n_sh), {n: stacked[n]
                                            for n in none_names},
            ccfg, mode=mode, in_alpha=_group_alpha(in_alpha, none_names),
            spec=spec)

    out = {}
    for n in stacked:
        if kinds[n] == "none":
            pcl = jax.tree_util.tree_map(lambda a: a[:, None],
                                         none_layers.pop(n))
            out[n] = ShardedPackedLayer(pcl, "none", 1)
        else:
            spl = ShardedPackedLayer(
                _stack([sl.pop(n) for sl in shard_layers], axis=1),
                kinds[n], n_sh)
            if mesh is not None:
                spl = place_packed_stack(spl, mesh, n_sh, shard_axis=1)
            out[n] = spl
    return out


def _resolve_mesh(arch_cfg, mesh, mesh_shape):
    """Resolve the (mesh, mesh_shape) pair a CIM deploy plans and places
    with: an explicit `mesh=` wins, else the arch's `cim_mesh` (the mesh
    the serving jits close over); `mesh_shape` defaults to the mesh's own
    axis sizes so the TP width and the placement cannot disagree — and an
    explicit mesh_shape that DOES disagree with the mesh's 'model' width
    raises here, before it becomes an opaque device_put divisibility
    error inside place_packed_stack."""
    mesh = mesh if mesh is not None else getattr(arch_cfg, "cim_mesh", None)
    if mesh_shape is None:
        mesh_shape = (dict(mesh.shape) if mesh is not None
                      else {"model": 1})
    elif mesh is not None \
            and int(mesh_shape.get("model", 1)) != dict(mesh.shape)["model"]:
        raise ValueError(
            f"mesh_shape {dict(mesh_shape)} disagrees with the serving "
            f"mesh's axes {dict(mesh.shape)}: per-shard chip stacks are "
            "placed with their shard dim on 'model', so the TP width must "
            "equal the mesh's 'model' size (drop mesh_shape to derive it "
            "from the mesh)")
    return mesh, dict(mesh_shape)


def deploy_transformer_cim(key, params, arch_cfg, *, mode: str = "ideal",
                           in_alpha: float = 3.0,
                           mesh_shape: Optional[Dict[str, int]] = None,
                           spec: Optional[CoreSpec] = None,
                           mesh=None, ccfg: Optional[CIMConfig] = None):
    """Compile every packed-servable projection of a transformer onto CIM
    chips and return params augmented with '<name>_cim' entries that
    models/transformer routes through when arch_cfg.cim_mode == "packed".

    Tensor parallelism: ONE ENGINE PER TP SHARD. Each shard of the 'model'
    mesh axis gets its own chip per transformer layer, compiled from that
    shard's local slice of every projection (distributed/sharding
    .param_pspecs + shard_slice — a NeuRRAM 'core' is an intra-shard
    unit). At serving time column-parallel shard outputs concatenate and
    row-parallel partial outputs psum over the 'model' axis inside the
    jit'd forward (ShardedPackedLayer -> sharded_packed_forward: under
    shard_map on a real mesh, unrolled in-process otherwise). Projections
    whose sharded dim is not divisible by the axis size fall back to a
    single replicated engine, mirroring distributed/sharding.fit_pspecs.

    mesh: optional real serving Mesh (launch/mesh.serving_mesh; defaults
    to arch_cfg.cim_mesh). DEVICE PLACEMENT HAPPENS HERE, AT DEPLOY TIME:
    every multi-shard chip stack is device_put with its shard dim on
    'model' (place_packed_stack), and MoE expert stacks land expert-
    parallel, so per-call serving never moves chip state.

    MoE expert stacks (ew_g/ew_i/ew_o, (L, E, d, de)): one chip per
    (layer, expert) — the paper's power-gated-core granularity — stacked
    back over E then L, and served through models/moe.moe_ffn's
    capacity-grouped dispatch (each routed group runs its own expert's
    packed dispatch; expert-parallel under shard_map on a real mesh).

    spec: CoreSpec threaded through to every compile_chip call.
    ccfg: optional caller-held CIMConfig, validated against the arch's CIM
    operating point (`arch_cim_config`) — a precision/IR-drop mismatch
    raises rather than silently deploying at a precision the forward pass
    does not serve.
    """
    if "layers" not in params or "wq" not in params["layers"]:
        raise ValueError(
            "deploy_transformer_cim covers dense attention+MLP stacks "
            "(params['layers']['wq']); recurrent archs (rwkv6 / mamba2) "
            "deploy through deploy_recurrent_cim")
    ccfg = arch_cim_config(arch_cfg, ccfg)
    spec = spec or CoreSpec()
    mesh, mesh_shape = _resolve_mesh(arch_cfg, mesh, mesh_shape)

    stacked = {n: params["layers"][n] for n in PACKED_PROJ_KEYS
               if n in params["layers"]}
    new_layers = dict(params["layers"])
    for n, spl in _deploy_sharded_stacks(
            key, stacked, ccfg, mode=mode, in_alpha=in_alpha,
            mesh_shape=mesh_shape, spec=spec, mesh=mesh).items():
        new_layers[n + "_cim"] = spl

    # routed-expert stacks: one chip per (layer, expert) — each expert's
    # (L, d, de) slice is itself a scanned layer stack, so reuse
    # deploy_packed_stack per expert and stack the results over E
    expert_w = {n: params["layers"][n] for n in PACKED_EXPERT_KEYS
                if n in params["layers"]}
    if expert_w:
        names = sorted(expert_w)
        n_experts = expert_w[names[0]].shape[1]
        per_exp = [deploy_packed_stack(
            jax.random.fold_in(key, 7919 + e),
            {n: expert_w[n][:, e] for n in names},
            ccfg, mode=mode, in_alpha=in_alpha, spec=spec)
            for e in range(n_experts)]
        n_model = int(mesh_shape.get("model", 1))
        for n in names:
            stack = _stack([pe.pop(n) for pe in per_exp], axis=1)
            if mesh is not None and n_model > 1 \
                    and n_experts % n_model == 0:
                # expert-parallel placement: the E dim is the shard axis
                stack = place_packed_stack(stack, mesh, n_model,
                                           shard_axis=1)
            new_layers[n + "_cim"] = stack

    out = dict(params)
    out["layers"] = new_layers
    if mesh is not None:
        out = place_replicated(out, mesh)
    # compile_chip verified each per-layer chip; this pass re-checks the
    # STACKED artifacts (trailing-dim shapes + shared static geometry)
    # after the tree_map(stack) / device placement surgery above
    return verify_deployed(out)


def is_recurrent_arch(arch_cfg) -> bool:
    """THE family predicate for CIM deployment — the one place that decides
    whether an arch's projections compile through deploy_recurrent_cim
    (rwkv6 / mamba2 stacks) or deploy_transformer_cim (dense / MoE)."""
    return bool(getattr(arch_cfg, "rwkv", False)) \
        or getattr(arch_cfg, "ssm_state", 0) > 0


def recurrent_proj_keys(arch_cfg) -> Tuple[str, ...]:
    """The projection names a recurrent arch compiles onto CIM chips."""
    if not is_recurrent_arch(arch_cfg):
        raise ValueError(
            f"{getattr(arch_cfg, 'name', arch_cfg)} is not a recurrent arch "
            "(expected rwkv=True or ssm_state > 0)")
    return RWKV_PROJ_KEYS if arch_cfg.rwkv else MAMBA_PROJ_KEYS


def deploy_cim(key, params, arch_cfg, **kw):
    """Family-dispatched CIM deploy: the single entry the serving driver
    calls (launch/steps.ArchServing.deploy_cim)."""
    if is_recurrent_arch(arch_cfg):
        return deploy_recurrent_cim(key, params, arch_cfg, **kw)
    return deploy_transformer_cim(key, params, arch_cfg, **kw)


def deploy_recurrent_cim(key, params, arch_cfg, *, mode: str = "ideal",
                         in_alpha: float = 3.0,
                         mesh_shape: Optional[Dict[str, int]] = None,
                         spec: Optional[CoreSpec] = None,
                         mesh=None, ccfg: Optional[CIMConfig] = None):
    """Compile a recurrent stack's projections onto CIM chips — the paper's
    versatility claim closed for serving: the same TNSA chips that serve
    CNNs/transformers serve the RWKV-6 and Mamba-2 stacks.

    Per layer, ONE chip carries every weight-stationary projection:

      * rwkv6: time-mix `wr/wk/wv/wg/wo` + channel-mix `ck/cv/cr`. The
        recurrent S update itself (diag(w) S + k v^T) stays digital float —
        it is state-dependent, so nothing is weight-stationary to program
        (the TNSA's BL->BL recurrent-MVM mode would stream S through the
        array; simulated-chip serving keeps it in the digital domain).
      * mamba2: fused `in_proj`/`out_proj` + the hybrid MLP `w_g/w_i/w_o`;
        the h update (decay h + dt B x^T) stays digital float likewise.
        The ONE weight-shared attention block of the zamba2 hybrid compiles
        its dense projections (wq/wk/wv/wo + MLP) on its own chip, served
        through the ordinary dense_block `cim_linear` routing.

    Tensor parallelism mirrors deploy_transformer_cim: one engine per
    'model'-axis shard via `_deploy_sharded_stacks` (device-resident on a
    real `mesh` — defaults to arch_cfg.cim_mesh — with shard_map
    execution at serve time); prefill (chunked scan) and O(1) decode both
    hit the packed Pallas kernel through the `cim_linear` dispatch in
    models/rwkv6 and models/mamba2.

    in_alpha is the scalar PACT clip for rms-norm-scale inputs; rwkv6's
    `cv` (driven by the squared-relu of the `ck` output) gets `in_alpha**2`
    via the per-name plumbing in `deploy_packed_stack`/`compile_chip`.
    """
    names = recurrent_proj_keys(arch_cfg)
    stacked = {n: params["layers"][n] for n in names
               if n in params["layers"]}
    if not stacked:
        raise ValueError("no recurrent projections found in "
                         f"params['layers'] (expected some of {names})")
    ccfg = arch_cim_config(arch_cfg, ccfg)
    spec = spec or CoreSpec()
    mesh, mesh_shape = _resolve_mesh(arch_cfg, mesh, mesh_shape)

    alphas: Dict[str, float] = {n: float(in_alpha) for n in stacked}
    if "cv" in alphas:          # squared-relu input range (see docstring)
        alphas["cv"] = float(in_alpha) ** 2

    new_layers = dict(params["layers"])
    for n, spl in _deploy_sharded_stacks(
            key, stacked, ccfg, mode=mode, in_alpha=alphas,
            mesh_shape=mesh_shape, spec=spec, mesh=mesh).items():
        new_layers[n + "_cim"] = spl
    out = dict(params)
    out["layers"] = new_layers

    # zamba2 hybrid: the ONE shared attention+MLP block (single weight
    # copy, no layer stack) — compile as an L=1 stack, then strip the
    # layer dim so dense_block's scan-free call sees unstacked engines
    # (placement happens AFTER the strip: the shard dim is then axis 0)
    if getattr(arch_cfg, "hybrid_attn_every", 0) > 0 \
            and "shared_attn" in params:
        sa = params["shared_attn"]
        sa_w = {n: sa[n][None] for n in PACKED_PROJ_KEYS if n in sa}
        sa_cim = _deploy_sharded_stacks(
            jax.random.fold_in(key, 104729), sa_w, ccfg, mode=mode,
            in_alpha=in_alpha, mesh_shape=mesh_shape, spec=spec)
        new_sa = dict(sa)
        for n, spl in sa_cim.items():
            spl = ShardedPackedLayer(
                jax.tree_util.tree_map(lambda a: a[0], spl.shards),
                spl.partition, spl.n_shards)
            if mesh is not None and spl.n_shards > 1:
                spl = place_packed_stack(spl, mesh, spl.n_shards,
                                         shard_axis=0)
            new_sa[n + "_cim"] = spl
        out["shared_attn"] = new_sa
    if mesh is not None:
        out = place_replicated(out, mesh)
    # re-verify the stacked artifacts post-stack/strip/placement (the
    # per-chip compiles were already strict-verified)
    return verify_deployed(out)


def deploy_rbm_cim(key, params, ccfg: CIMConfig, v_cal, *,
                   mode: str = "relaxed", interleave: bool = False,
                   spec: Optional[CoreSpec] = None):
    """Compile an RBM onto ONE bidirectional chip — the fourth serving
    surface on `CompiledChip` and the first consumer of transpose-direction
    packing (paper Fig. 4e-g, Bayesian image recovery).

    The augmented (V+1, H+1) array (bias vectors embedded via the
    always-on-unit trick) goes through the full chip-compiler pipeline ONCE
    with directions=("fwd", "bwd"): v->h runs SL->BL, h->v runs BL->SL over
    the same programmed conductances, each direction carrying its own
    per-tile ADC calibration measured on training-set-driven activations
    (visibles forward, a software half-step's hiddens backward).

    interleave=True applies the paper's Fig. 4f pixel-interleaved
    multi-core mapping as a PLAN OPTION: visible rows are permuted so core
    k holds units {k, k + n_cores, ...} — every core sees a strided,
    down-sampled version of the whole image, equalizing per-core output
    dynamic range before per-core calibration. The permutation is realized
    as a custom stage-1 Plan handed to `compile_chip` (rows padded to equal
    per-core bins so the packed block geometry stays aligned); the Gibbs
    loop gathers inputs / scatters outputs by the stored permutation inside
    its jit.

    Returns `models/rbm.ChipRBM`; serve with `rbm.chip_gibbs_recover` or
    `launch/recover.py`.
    """
    from . import rbm
    from ..core.mapping import (Plan, Tile, interleave_assignment,
                                ir_drop_max_cols)
    spec = spec or CoreSpec()
    n_vis, n_hid = params["w"].shape
    w_aug = rbm._augmented(params)             # (V+1, H+1)
    n_units, n_cols = w_aug.shape
    row_cap = spec.rows // 2                   # differential weight rows
    perm = inv_perm = None
    plan = None
    n_pad = n_units
    if interleave:
        n_blocks = -(-n_units // row_cap)
        bs = -(-n_units // n_blocks)           # equal per-core bins
        n_pad = n_blocks * bs                  # pad with inert zero rows
        assign = interleave_assignment(n_pad, n_blocks)
        perm = jnp.argsort(assign)             # stable: bin k = units = k (mod n_blocks)
        inv_perm = jnp.argsort(perm)
        w_dep = jnp.zeros((n_pad, n_cols)).at[:n_units].set(w_aug)[perm]
        # the custom plan owns the constraints plan_chip would have
        # applied: keep the IR-drop vertical-split bound in force
        col_cap = min(spec.cols, ir_drop_max_cols(ccfg, spec) or spec.cols)
        n_cblocks = -(-n_cols // col_cap)
        tiles = [Tile("rbm", row0=i * bs, col0=j * col_cap, rows=bs,
                      cols=min(col_cap, n_cols - j * col_cap),
                      core=i * n_cblocks + j)
                 for i in range(n_blocks) for j in range(n_cblocks)]
        if len(tiles) > spec.n_cores:
            raise ValueError(f"interleaved RBM needs {len(tiles)} cores "
                             f"> {spec.n_cores} available")
        plan = Plan(tiles=tiles, n_cores_used=len(tiles), duplicated={},
                    merged=[])
    else:
        w_dep = w_aug

    # training-set-driven calibration for BOTH directions (Ext. Data
    # Fig. 5): visibles drive the fwd distribution, hiddens from a software
    # half-step drive the bwd one
    xv = rbm._aug_v(v_cal)
    if n_pad > xv.shape[1]:
        xv = jnp.pad(xv, ((0, 0), (0, n_pad - xv.shape[1])))
    if perm is not None:
        xv = xv[:, perm]
    ph = jax.nn.sigmoid(v_cal @ params["w"] + params["b"])
    xh = rbm._aug_h((ph > 0.5).astype(jnp.float32))

    chip = cim_api.compile_chip(
        key, {"rbm": w_dep.astype(jnp.float32)}, ccfg, spec, mode,
        plan=plan, in_alpha=1.0, x_cal={"rbm": xv},
        directions=("fwd", "bwd"), in_alpha_bwd=1.0, x_cal_bwd={"rbm": xh})
    return verify_deployed(rbm.ChipRBM(
        chip=chip, perm=perm, inv_perm=inv_perm,
        n_vis=n_vis, n_hid=n_hid, n_pad=n_pad))

"""Packed-tile CIM execution engine: the single-dispatch executor
(pack_tiles + multicore_mvm_packed + CIMEngine) must match the per-tile
loop executor bitwise on exact modes, stay within tolerance on stochastic
modes, and trace exactly once per plan shape."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container — deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

import repro.core as core
from repro.core.types import CIMConfig, CoreSpec
from repro.core.conductance import weights_to_conductances
from repro.core.mapping import (MatrixReq, plan_layers, pack_tiles,
                                multicore_mvm, multicore_mvm_packed,
                                join_tile_stacks, pack_tiles_transposed,
                                schedule_tiles, slice_tile_stacks,
                                split_tile_stacks, take)
from repro.kernels.cim_mvm.ops import cim_mvm
from repro.kernels.cim_mvm.kernel import TRACE_COUNTS


def _cim_setup(r, c, b=4, seed=0, cfg=None):
    cfg = cfg or CIMConfig(in_bits=4, out_bits=8)
    k = jax.random.PRNGKey(seed)
    w = jax.random.normal(k, (r, c)) * 0.1
    cond = weights_to_conductances(w, cfg.device)
    x = jax.random.randint(jax.random.fold_in(k, 1), (b, r), -7, 8)
    return cfg, w, cond, x


def _loop_counts(x_int, cond, tiles, vd, cfg):
    """Reference per-tile loop executor: one cim_mvm per tile, counts
    accumulated digitally across row splits (the pre-packed hot path)."""
    def matmul_fn(xt, _wt, t):
        gp = jax.lax.dynamic_slice(cond.g_pos, (t.row0, t.col0),
                                   (t.rows, t.cols))
        gn = jax.lax.dynamic_slice(cond.g_neg, (t.row0, t.col0),
                                   (t.rows, t.cols))
        return cim_mvm(xt, gp, gn, vd, cfg)
    return multicore_mvm(x_int, cond.g_pos - cond.g_neg, tiles, matmul_fn)


# ------------------------------------------------------ generic (identity)

@settings(max_examples=8, deadline=None)
@given(r=st.integers(10, 300), c=st.integers(10, 300), seed=st.integers(0, 99))
def test_packed_identity_matches_matmul(r, c, seed):
    """Property: packed executor == loop executor == x @ W for exact tiles,
    including non-divisible shapes (zero padding must be value-preserving)."""
    k = jax.random.PRNGKey(seed)
    w = jax.random.normal(k, (r, c))
    x = jax.random.normal(jax.random.fold_in(k, 1), (4, r))
    tiles = plan_layers([MatrixReq("m", r, c)]).tiles_for("m")
    packed = pack_tiles(tiles, w)
    y = multicore_mvm_packed(x, packed)
    y_loop = multicore_mvm(x, w, tiles, lambda xt, wt, t: xt @ wt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), rtol=2e-4,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_loop), rtol=1e-5,
                               atol=1e-4)


# ------------------------------------------------- CIM datapath, plan zoo

def _plan_for(kind):
    """(reqs, spec, target) triples covering the paper's mapping cases."""
    if kind == "split":
        return [MatrixReq("m", 300, 500)], CoreSpec(), "m"
    if kind == "duplicate":
        return [MatrixReq("hot", 100, 60, intensity=8.0),
                MatrixReq("cold", 64, 32)], CoreSpec(), "hot"
    if kind == "merge":
        reqs = [MatrixReq(f"s{i}", 30, 40, intensity=0.5) for i in range(6)]
        reqs.append(MatrixReq("m", 200, 70))
        return reqs, CoreSpec(n_cores=6), "m"
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["split", "duplicate", "merge"])
def test_packed_counts_match_loop_bitwise(kind):
    """Exact mode: the packed single-dispatch executor reproduces the loop
    executor's ADC counts bitwise across split/duplicate/merge plans."""
    reqs, spec, target = _plan_for(kind)
    plan = plan_layers(reqs, spec)
    tiles = plan.tiles_for(target)
    rows = max(t.row0 + t.rows for t in tiles)
    cols = max(t.col0 + t.cols for t in tiles)
    cfg, w, cond, x = _cim_setup(rows, cols)
    vd = 0.002
    packed = pack_tiles(tiles, cond.g_pos - cond.g_neg,
                        gsum=cond.g_pos + cond.g_neg, v_decr=vd)
    y_packed = multicore_mvm_packed(x, packed, cfg)
    y_loop = _loop_counts(x, cond, tiles, vd, cfg)
    np.testing.assert_array_equal(np.asarray(y_packed), np.asarray(y_loop))


@pytest.mark.parametrize("activation", ["relu", "tanh", "sigmoid"])
def test_packed_activations_match_loop(activation):
    """Fused activation epilogues survive packing (per-tile activation then
    digital accumulation — identical semantics to the loop executor)."""
    cfg = dataclasses.replace(CIMConfig(in_bits=4, out_bits=8),
                              activation=activation)
    cfg, w, cond, x = _cim_setup(200, 70, cfg=cfg)
    tiles = plan_layers([MatrixReq("m", 200, 70)]).tiles_for("m")
    vd = 0.002
    packed = pack_tiles(tiles, cond.g_pos - cond.g_neg,
                        gsum=cond.g_pos + cond.g_neg, v_decr=vd)
    y_packed = multicore_mvm_packed(x, packed, cfg)
    y_loop = _loop_counts(x, cond, tiles, vd, cfg)
    np.testing.assert_array_equal(np.asarray(y_packed), np.asarray(y_loop))


def test_packed_stochastic_within_tolerance():
    """Stochastic activation draws per-(block, tile) hash noise — packed and
    loop executors can't match bitwise, but sampling statistics must agree."""
    cfg = dataclasses.replace(CIMConfig(in_bits=4, out_bits=8),
                              activation="stochastic")
    w = jnp.ones((160, 32)) * 0.1        # 2 row tiles, sign follows input
    cond = weights_to_conductances(w, cfg.device)
    tiles = plan_layers([MatrixReq("m", 160, 32)]).tiles_for("m")
    packed = pack_tiles(tiles, cond.g_pos - cond.g_neg,
                        gsum=cond.g_pos + cond.g_neg, v_decr=0.01)
    means_packed, means_loop = [], []
    for v in (-7, 0, 7):
        x = jnp.full((64, 160), v, jnp.int32)
        means_packed.append(float(multicore_mvm_packed(x, packed, cfg).mean()))
        means_loop.append(float(_loop_counts(x, cond, tiles, 0.01, cfg).mean()))
    assert means_packed[0] < means_packed[1] < means_packed[2]
    np.testing.assert_allclose(means_packed, means_loop, atol=0.15)


# ------------------------------------------------------------- CIMEngine

def test_engine_matches_per_tile_reference():
    """CIMEngine's de-normalized digital accumulation == per-tile loop with
    per-core calibration + de-normalization (counts * norm_t * v_decr_t
    summed over row splits)."""
    cfg = CIMConfig(in_bits=4, out_bits=8)
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (300, 120))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 300))
    x_cal = jax.random.normal(jax.random.PRNGKey(5), (64, 300))
    eng = core.CIMEngine(cfg, mode="ideal")
    eng.program(jax.random.PRNGKey(2), {"a": w}, in_alpha=2.0,
                x_cal={"a": x_cal})
    y = eng.forward("a", x)

    layer = eng.layers["a"].layer
    tiles = eng.plan.tiles_for("a")
    vds = core.calibrate_tile_v_decr(layer, tiles, x_cal, cfg)
    vd_by_tile = {(t.row0, t.col0): vds[i] for i, t in enumerate(tiles)}
    x_int, scale = core.quantize_to_int(x, layer.in_alpha, cfg.in_bits)

    def matmul_fn(xt, _wt, t):
        gp = jax.lax.dynamic_slice(layer.g_pos, (t.row0, t.col0),
                                   (t.rows, t.cols))
        gn = jax.lax.dynamic_slice(layer.g_neg, (t.row0, t.col0),
                                   (t.rows, t.cols))
        vd = vd_by_tile[(t.row0, t.col0)]
        counts = cim_mvm(xt, gp, gn, vd, cfg)
        norm_t = jnp.sum(gp + gn, axis=0)
        return counts * norm_t[None, :] * vd

    acc = multicore_mvm(x_int, layer.g_pos - layer.g_neg, tiles, matmul_fn)
    y_ref = acc * layer.w_max * scale / (cfg.v_read * cfg.device.g_max)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)
    # and it tracks the ideal clipped matmul
    yt = jnp.clip(x, -2, 2) @ w
    corr = np.corrcoef(np.asarray(y).ravel(), np.asarray(yt).ravel())[0, 1]
    assert corr > 0.97


def test_per_tile_adc_calibration_beats_whole_matrix():
    """Split plans need per-core v_decr: the whole-matrix step mis-scales
    each tile's ADC range (the chip calibrates per core for this reason)."""
    cfg = CIMConfig(in_bits=4, out_bits=8)
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (300, 120))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 300))
    x_cal = jax.random.normal(jax.random.PRNGKey(5), (64, 300))
    eng = core.CIMEngine(cfg, mode="ideal")
    eng.program(jax.random.PRNGKey(2), {"a": w}, in_alpha=2.0,
                x_cal={"a": x_cal})
    y_tile = eng.forward("a", x)
    layer = eng.layers["a"].layer
    tiles = eng.plan.tiles_for("a")
    y_scalar = core.packed_forward(core.pack_cim_layer(layer, tiles, cfg),
                                   x, cfg)    # whole-matrix v_decr fallback
    yt = jnp.clip(x, -2, 2) @ w
    e_tile = float(jnp.linalg.norm(y_tile - yt))
    e_scalar = float(jnp.linalg.norm(y_scalar - yt))
    assert e_tile < 0.9 * e_scalar


def test_engine_reprogram_discards_stale_layers():
    """Re-programming replaces the chip state: layers from the previous
    program() must not stay servable against a discarded plan."""
    cfg = CIMConfig(in_bits=4, out_bits=8)
    eng = core.CIMEngine(cfg, mode="ideal")
    eng.program(jax.random.PRNGKey(0),
                {"a": 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                              (64, 32))})
    eng.program(jax.random.PRNGKey(0),
                {"b": 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                              (48, 16))})
    assert "a" not in eng and "b" in eng
    with pytest.raises(KeyError):
        eng.forward("a", jnp.zeros((2, 64)))


def test_engine_single_trace_per_plan_shape():
    """The serving property the refactor exists for: repeated batched
    forwards through one plan cost ONE kernel trace (per input shape)."""
    cfg = CIMConfig(in_bits=4, out_bits=8)
    # shapes unique to this test: the kernel jit cache is process-global
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (310, 130))
    eng = core.CIMEngine(cfg, mode="ideal")
    eng.program(jax.random.PRNGKey(1), {"a": w}, in_alpha=2.0)
    before = TRACE_COUNTS["cim_mvm_packed"]
    for s in range(5):
        eng.forward("a", jax.random.normal(jax.random.PRNGKey(s), (9, 310)))
    assert TRACE_COUNTS["cim_mvm_packed"] - before == 1
    # a new batch shape is a new trace — but only one
    for s in range(3):
        eng.forward("a", jax.random.normal(jax.random.PRNGKey(s), (17, 310)))
    assert TRACE_COUNTS["cim_mvm_packed"] - before == 2


def test_engine_rejects_oracle_only_configs():
    """Wire IR / coupling / ADC-offset spread still need the bit-serial
    oracle; IR drop no longer does — the planner mitigates it with
    vertical column splits (mapping.ir_drop_max_cols), so the engine
    accepts such configs and plans narrower tiles."""
    for ni in (core.NonIdealityConfig(coupling_sigma=0.1),
               core.NonIdealityConfig(wire_r_alpha=1e-4),
               core.NonIdealityConfig(adc_offset_sigma=0.01)):
        with pytest.raises(ValueError):
            core.CIMEngine(CIMConfig(in_bits=4, out_bits=8, nonideal=ni))
    cfg = CIMConfig(in_bits=4, out_bits=8,
                    nonideal=core.NonIdealityConfig(ir_drop_alpha=2e-7))
    eng = core.CIMEngine(cfg, mode="ideal")
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (100, 200))
    plan = eng.program(jax.random.PRNGKey(1), {"a": w})
    cap = core.ir_drop_max_cols(cfg)
    assert max(t.cols for t in plan.tiles_for("a")) <= cap
    assert len(plan.tiles_for("a")) > 1


def test_engine_multi_layer_plan_shares_cores():
    """Engine plans all matrices together (split/duplicate/merge on one
    chip) and serves each through its own packed dispatch."""
    cfg = CIMConfig(in_bits=4, out_bits=8)
    k = jax.random.PRNGKey(0)
    ws = {"hot": 0.1 * jax.random.normal(k, (100, 60)),
          "cold": 0.1 * jax.random.normal(jax.random.fold_in(k, 1), (64, 32))}
    reqs = [MatrixReq("hot", 100, 60, intensity=8.0),
            MatrixReq("cold", 64, 32)]
    eng = core.CIMEngine(cfg, mode="ideal")
    plan = eng.program(jax.random.PRNGKey(1), ws, reqs=reqs, in_alpha=2.0)
    assert plan.duplicated.get("hot", 0) >= 1
    for i, (name, w) in enumerate(sorted(ws.items())):
        x = jax.random.normal(jax.random.fold_in(k, 10 + i),
                              (4, w.shape[0]))
        y = eng.forward(name, x)
        yt = jnp.clip(x, -2, 2) @ w
        corr = np.corrcoef(np.asarray(y).ravel(),
                           np.asarray(yt).ravel())[0, 1]
        assert corr > 0.95


# ------------------------------------------------ tile stacks read in place

def _plan_stack(kind, n_layers, n_shards):
    """An (n_layers, n_shards) stack of `kind` plans, each over conductances
    of its own, stacked the way deploy stacks a layer scan's plans.
    Returns (stack, input width)."""
    # a 200 x 500 matrix on 2 cores time-shares them: a multi-pass plan
    reqs, spec, target = (([MatrixReq("m", 200, 500)], CoreSpec(n_cores=2),
                           "m") if kind == "scheduled" else _plan_for("split"))
    tiles = plan_layers(reqs, spec).tiles_for(target)
    rows = max(t.row0 + t.rows for t in tiles)
    cols = max(t.col0 + t.cols for t in tiles)
    sched = schedule_tiles(tiles) if kind == "scheduled" else None
    plans = []
    for i in range(n_layers * n_shards):
        _, _, cond, _ = _cim_setup(rows, cols, seed=10 + i)
        gsum = cond.g_pos + cond.g_neg
        plan = pack_tiles(tiles, cond.g_pos - cond.g_neg, gsum=gsum,
                          v_decr=0.002, schedule=sched)
        if kind == "transposed":
            plan = pack_tiles_transposed(tiles, plan, gsum=gsum,
                                         v_decr=0.002, schedule=sched)
        plans.append(plan)
    stack = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a).reshape((n_layers, n_shards) + a[0].shape),
        *plans)
    if kind == "scheduled":
        assert stack.n_passes > 1
    return stack, cols if kind == "transposed" else rows


@pytest.mark.parametrize("kind", ["packed", "scheduled", "transposed"])
@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("n_layers", [2, 3])
def test_kernel_indexes_tile_stack_bitwise(n_layers, n_shards, kind):
    """Each kernel reading an (L, n_shards, T, bk, bn) tile stack in place
    at every (layer, shard) gives, bitwise, what it gives on that
    position's sliced plan — and the positions differ, so the index picks
    the tiles."""
    stack, n_in = _plan_stack(kind, n_layers, n_shards)
    cfg = CIMConfig(in_bits=4, out_bits=8)
    x = jax.random.randint(jax.random.PRNGKey(5), (4, n_in), -7, 8)
    tree, stacks = split_tile_stacks(stack)
    assert tree.gd_tiles is None and stacks == [stack.gd_tiles]
    bound = join_tile_stacks(tree, stacks)
    outs = []
    for li in range(n_layers):
        layer = take(bound, li)
        np.testing.assert_array_equal(slice_tile_stacks(layer).gd_tiles,
                                      stack.gd_tiles[li])
        for s in range(n_shards):
            in_place = take(layer, s)
            assert in_place.gd_tiles is stack.gd_tiles
            assert int(in_place.stack_index) == li * n_shards + s
            sliced = jax.tree_util.tree_map(lambda a: a[li, s], stack)
            y = np.asarray(multicore_mvm_packed(x, in_place, cfg))
            np.testing.assert_array_equal(
                y, np.asarray(multicore_mvm_packed(x, sliced, cfg)))
            outs.append(y.tobytes())
    assert len(set(outs)) == n_layers * n_shards


def test_stacked_plan_needs_one_position():
    """A plan spanning several stack positions, or a stack with no index,
    is refused instead of silently running position 0."""
    stack, n_in = _plan_stack("packed", 2, 1)
    cfg = CIMConfig(in_bits=4, out_bits=8)
    x = jnp.zeros((4, n_in))
    with pytest.raises(ValueError, match="index one layer"):
        multicore_mvm_packed(x, stack, cfg)
    bound = join_tile_stacks(*split_tile_stacks(stack))
    with pytest.raises(ValueError, match="still spans"):
        multicore_mvm_packed(x, take(bound, 0), cfg)

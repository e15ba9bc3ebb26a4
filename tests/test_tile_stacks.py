"""Layer scans read each projection's packed tiles in place from the
deployed stack (models/transformer.scan_layers): no tile stack is among a
layer scan's scanned operands, a step's outputs are bitwise those of the
scan that slices every layer's tiles, and every packed dispatch of a step
indexes the stack."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
import repro.models.mamba2 as mamba2
import repro.models.transformer as T
from repro.core.mapping import PackedPlan
from repro.launch.scheduler import count_packed_dispatches
from repro.launch.steps import arch_serving
from repro.obs import MetricsRegistry


def _slice_tiles(monkeypatch):
    """Make the layer loops slice every layer's tiles out of the stack:
    plans keep their tile stacks in the scanned operands."""
    for mod in (T, mamba2):
        monkeypatch.setattr(mod, "split_tile_stacks", lambda tree: (tree, []))
        monkeypatch.setattr(mod, "join_tile_stacks", lambda tree, _: tree)


def _deployed(arch, **overrides):
    cfg = configs.get(arch, smoke=True).replace(
        dtype=jnp.float32, cim_mode="packed", **overrides)
    sv = arch_serving(cfg)
    params = sv.deploy_cim(jax.random.PRNGKey(7),
                           sv.init_params(jax.random.PRNGKey(0)),
                           mode="ideal", mesh_shape={"model": 1})
    return cfg, sv, params


def _plans(tree):
    return [leaf for leaf in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, PackedPlan))
        if isinstance(leaf, PackedPlan)]


def _scans(jaxpr):
    """Every scan equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, tuple) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scans(sub)


def _scan_operand_shapes(fn, *args):
    """(shapes of the scanned xs, shapes of the consts) over every scan of
    a fresh trace of `fn`."""
    xs, consts = set(), set()
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr
    for eqn in _scans(jaxpr):
        n_c, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
        consts |= {tuple(v.aval.shape) for v in eqn.invars[:n_c]}
        xs |= {tuple(v.aval.shape) for v in eqn.invars[n_c + n_carry:]}
    return xs, consts


def _stack_views(cfg, shapes):
    """Tile stack shapes as a layer scan would see them: whole, or cut
    into groups of layers and a remainder (mamba2's hybrid groups)."""
    views = set(shapes)
    if cfg.hybrid_attn_every:
        n_groups, rem = divmod(cfg.n_layers, cfg.hybrid_attn_every)
        for s in shapes:
            views |= {(n_groups, cfg.hybrid_attn_every) + s[1:],
                      (rem,) + s[1:]}
    return views


def _serve(sv, params, toks, gauge):
    """Jitted prefill then one decode step, fresh jits (nothing cached
    from another scan); the decode step's dispatches land in `gauge`."""
    prefill = jax.jit(lambda p, s, t: sv.prefill(p, s, t))
    decode = jax.jit(count_packed_dispatches(
        lambda p, s, t: sv.decode_step(p, s, t), gauge, "decode"))
    lg_p, state = prefill(params, sv.init_state(2, 32), toks[:, :-1])
    lg_d, state = decode(params, state, toks[:, -1:])
    return lg_p, lg_d, state


@pytest.mark.parametrize("arch,overrides", [
    ("codeqwen1.5-7b", {}),
    ("rwkv6-7b", {}),
    # 5 layers in groups of 3: a grouped scan and a remainder scan
    ("zamba2-7b", {"n_layers": 5})])
def test_step_reads_tile_stacks_in_place(arch, overrides, monkeypatch):
    cfg, sv, params = _deployed(arch, **overrides)
    tiles = {p.gd_tiles.shape for p in _plans(params["layers"])}
    views = _stack_views(cfg, tiles)
    n_dispatch = len(_plans(params["layers"])) * cfg.n_layers
    # zamba2's one shared attention block is no layer stack: its plans
    # hold their own tiles, one block after each group of layers
    n_shared = len(_plans(params.get("shared_attn", {}))) \
        * (cfg.n_layers // max(cfg.hybrid_attn_every, 1))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab)
    state = sv.init_state(2, 32)

    reg = MetricsRegistry()
    gauge = reg.gauge("dispatches")
    got = _serve(sv, params, toks, gauge)
    assert gauge.value(entry="decode", tile_read="in_place") == n_dispatch
    assert gauge.value(entry="decode", tile_read="sliced") == n_shared
    xs, consts = _scan_operand_shapes(sv.decode_step, params, state,
                                      toks[:, -1:])
    assert not views & xs, f"tile stacks scanned as xs: {views & xs}"
    assert tiles <= consts

    _slice_tiles(monkeypatch)
    want = _serve(sv, params, toks, gauge)
    assert gauge.value(entry="decode", tile_read="in_place") == 0
    assert gauge.value(entry="decode", tile_read="sliced") \
        == n_dispatch + n_shared
    xs, _ = _scan_operand_shapes(sv.decode_step, params, state,
                                 toks[:, -1:])
    assert views & xs           # the check sees a slicing scan's stacks
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

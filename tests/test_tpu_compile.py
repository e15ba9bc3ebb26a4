"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each case lowers a kernel from shapes alone for a v5e chip
that is described, not attached, and asserts that the compiled HLO calls
a Mosaic kernel (`tpu_custom_call`). Interpret-mode tests cannot see what
only the TPU compiler refuses — the stochastic epilogue's uint32 -> f32
cast was one such case. Shapes are codeqwen1.5-7b's: wq (4096 x 4096,
512 tiles of 128x256) and w_o (13440 x 4096, 1680 tiles) at 8 decode
rows. The topology is described inside a fixture, so every test worker
collects the same tests and only the worker running this file loads the
TPU compiler. The whole pool decode step is compiled too, at codeqwen's
and rwkv6's published widths with 2 layers, to show that its kernels read
each layer's tiles in place from the scanned stack.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import mapping
from repro.core.types import CIMConfig, CoreSpec
from repro.kernels.cim_mvm import ops
from repro.kernels.cim_mvm.ops import packed_call
from repro.kernels.noisy_matmul.kernel import noisy_matmul_pallas
from repro.launch.scheduler import count_packed_dispatches, init_pool
from repro.launch.steps import arch_serving, make_pool_decode_step
from repro.obs import MetricsRegistry


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _plan(rows, cols, *, n_cores=8192, fold_norm=True):
    """PackedPlan of an (rows, cols) matrix as shapes only (eval_shape)."""
    plan = mapping.plan_layers([mapping.MatrixReq("w", rows, cols)],
                               spec=CoreSpec(n_cores=n_cores))
    tiles = plan.tiles_for("w")
    sched = mapping.schedule_tiles(tiles)
    g = jax.ShapeDtypeStruct((rows, cols), jnp.float32)
    packed = jax.eval_shape(lambda gd: mapping.pack_tiles(
        tiles, gd, gsum=gd, v_decr=1.0, fold_norm=fold_norm,
        schedule=sched), g)
    return tiles, sched, packed


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _packed_text(one_chip, packed, n_in, activation="none"):
    cfg = CIMConfig()
    x = jax.ShapeDtypeStruct((8, n_in), jnp.float32, sharding=one_chip)
    return _compiled_text(
        lambda x_, p: packed_call(x_, p, activation=activation,
                                  n_max=cfg.out_mag_levels,
                                  v_read=cfg.v_read, interpret=False),
        x, _on(one_chip, packed))


@pytest.mark.parametrize("name,rows,cols,n_tiles", [
    ("wq", 4096, 4096, 512), ("w_o", 13440, 4096, 1680)])
def test_packed_kernel_compiles_at_codeqwen_widths(one_chip, name, rows,
                                                   cols, n_tiles):
    _, _, packed = _plan(rows, cols)
    assert packed.n_tiles == n_tiles and packed.n_passes == 1
    assert "tpu_custom_call" in _packed_text(one_chip, packed, rows)


def test_scheduled_kernel_compiles_on_merged_plan(one_chip):
    _, _, packed = _plan(512, 1024, n_cores=8)      # 16 tiles on 8 cores
    assert packed.n_passes > 1                       # merged: scheduled
    assert "tpu_custom_call" in _packed_text(one_chip, packed, 512)


def test_transposed_kernel_compiles(one_chip):
    tiles, sched, fwd = _plan(1024, 512, fold_norm=False)
    g = jax.ShapeDtypeStruct((1024, 512), jnp.float32)
    bwd = jax.eval_shape(lambda p, gd: mapping.pack_tiles_transposed(
        tiles, p, gsum=gd, v_decr=1.0, schedule=sched), fwd, g)
    assert bwd.transpose
    assert "tpu_custom_call" in _packed_text(one_chip, bwd, 512)


def test_stochastic_epilogue_compiles(one_chip):
    """Regression: the hash PRNG's uint32 -> f32 cast has no Mosaic
    lowering; hash_uniform goes through int32."""
    _, _, packed = _plan(128, 4096, fold_norm=False)
    assert "tpu_custom_call" in _packed_text(one_chip, packed, 128,
                                             activation="stochastic")


def test_noisy_matmul_compiles(one_chip):
    """The training kernel draws its weight noise from the same PRNG."""
    x = jax.ShapeDtypeStruct((256, 1024), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1024, 512), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    seed = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda *a: noisy_matmul_pallas(*a, interpret=False), x, w, s, seed)
    assert "tpu_custom_call" in text


# an instruction whose value is an array of 128 x 256 tiles and which is
# neither an operand of the step, a view of one, nor a tuple element: a
# copy (or slice) of tiles made before a kernel reads them
TILE_COPY = re.compile(r"= f32\[[\d,]*,128,256\]\S* "
                       r"(?!parameter\(|bitcast\(|get-tuple-element\()"
                       r"[\w-]+\(")


@pytest.mark.parametrize("arch,widths,n_dispatch", [
    ("codeqwen1.5-7b", dict(vocab=92416, n_kv_heads=4, d_head=128,
                            qkv_bias=True, rope_theta=1e6), 14),
    ("rwkv6-7b", {}, 16)])
def test_pool_decode_reads_tile_stacks_in_place(one_chip, monkeypatch, arch,
                                                widths, n_dispatch):
    """The compiled pool decode step (32 slots of 640 tokens, 2 layers at
    published widths) holds no dynamic-slice or copy of a tile stack:
    every packed dispatch, 7 or 8 projections x 2 layers, indexes the
    scanned stack in place, as the engine's gauge reports."""
    cfg = configs.get(arch).replace(n_layers=2, cim_mode="packed",
                                    dtype=jnp.float32, **widths)
    sv = arch_serving(cfg)
    params = jax.eval_shape(lambda: sv.deploy_cim(
        jax.random.PRNGKey(1), sv.init_params(jax.random.PRNGKey(0)),
        mode="ideal", spec=CoreSpec(rows=256, cols=256, n_cores=8192)))
    pool = jax.eval_shape(lambda: init_pool(cfg, 32, 640))
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    gauge = MetricsRegistry().gauge("serve_packed_dispatches")
    step = count_packed_dispatches(make_pool_decode_step(cfg), gauge,
                                   "pool_decode")
    text = jax.jit(step, donate_argnums=(1,)).lower(
        _on(one_chip, params), _on(one_chip, pool)).compile().as_text()
    assert gauge.value(entry="pool_decode", tile_read="in_place") \
        == n_dispatch
    assert gauge.value(entry="pool_decode", tile_read="sliced") == 0
    kernels = re.findall(r"%(cim_mvm_packed_pallas\.\d+) = ", text)
    assert len(kernels) == n_dispatch // 2       # one scan body, 2 layers
    copies = [line.strip()[:160] for line in text.splitlines()
              if TILE_COPY.search(line)]
    assert not copies, copies

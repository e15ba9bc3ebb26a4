"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each case lowers a kernel from shapes alone for a v5e chip
that is described, not attached, and asserts that the compiled HLO calls
a Mosaic kernel (`tpu_custom_call`). Interpret-mode tests cannot see what
only the TPU compiler refuses — the stochastic epilogue's uint32 -> f32
cast was one such case. Shapes are codeqwen1.5-7b's: wq (4096 x 4096,
512 tiles of 128x256) and w_o (13440 x 4096, 1680 tiles) at 8 decode
rows. The topology is described inside a fixture, so every test worker
collects the same tests and only the worker running this file loads the
TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mapping
from repro.core.types import CIMConfig, CoreSpec
from repro.kernels.cim_mvm.ops import packed_call
from repro.kernels.noisy_matmul.kernel import noisy_matmul_pallas


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

"""Real-mesh TP serving: shard_map packed execution over the device mesh.

Two layers of coverage:

  * in-process tests (single device): the serving-mesh factoring rule and
    builder, the NamedSharding producers for packed shard stacks, the
    loop fallback contract of `sharded_packed_forward`, and the
    `deploy_packed_stack` per-name in_alpha validation.
  * a SUBPROCESS test on 8 forced host devices
    (tests/_mesh_parity_child.py): the shard_map executor is bitwise-equal
    to the unrolled-loop oracle for col / row / none partitions including
    multi-pass scheduled and IR-drop split plans, costs one kernel trace
    per plan, and serves from deploy-time-placed (device-resident) chip
    stacks — MoE expert-parallel dispatch included. A subprocess because
    XLA_FLAGS=--xla_force_host_platform_device_count must land before jax
    first initializes, and the rest of the suite needs the real count.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


# ------------------------------------------------------- mesh construction

def test_serving_mesh_shape_factoring(monkeypatch):
    """The documented rule: largest power of two dividing the device count
    (capped at max_model) goes to 'model'; odd factors land on 'data'."""
    from repro.launch import mesh as M
    for n, want in [(1, {"data": 1, "model": 1}),
                    (3, {"data": 3, "model": 1}),
                    (6, {"data": 3, "model": 2}),
                    (8, {"data": 1, "model": 8}),
                    (12, {"data": 3, "model": 4}),
                    (64, {"data": 4, "model": 16})]:   # max_model cap
        monkeypatch.setattr(jax, "device_count", lambda n=n: n)
        assert M.serving_mesh_shape() == want, n
    monkeypatch.setattr(jax, "device_count", lambda: 8)
    assert M.serving_mesh_shape(max_model=2) == {"data": 4, "model": 2}


def test_serving_mesh_builder():
    """serving_mesh() returns a real Mesh matching the factoring — on this
    (single-device unless forced) suite process, a 1x1 or DxM mesh whose
    axis sizes multiply to the device count."""
    from repro.launch.mesh import serving_mesh, serving_mesh_shape
    mesh = serving_mesh()
    assert tuple(mesh.axis_names) == ("data", "model")
    shape = dict(mesh.shape)
    assert shape == serving_mesh_shape()
    assert shape["data"] * shape["model"] == jax.device_count()


def test_packed_pspecs_shard_axis():
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import packed_pspecs
    tree = {"a": jnp.zeros((2, 4, 3, 5)), "b": jnp.zeros((4, 7))}
    specs = packed_pspecs(tree, n_shards=4, shard_axis=1)
    assert specs["a"] == P(None, "model", None, None)
    # n_shards == 1 (replicated 'none' stacks): fully replicated
    specs1 = packed_pspecs(tree, n_shards=1, shard_axis=1)
    assert specs1["a"] == P(None, None, None, None)
    specs0 = packed_pspecs(tree, n_shards=4, shard_axis=0)
    assert specs0["b"] == P("model", None)


# ----------------------------------------------- fallback + validation

def _dense_deploy(n_shards, **cfg_kw):
    import repro.configs as configs
    import repro.models.transformer as T
    import repro.models.nn as nn
    cfg = configs.get("gemma2-9b", smoke=True).replace(
        dtype=jnp.float32, cim_mode="packed", n_layers=1, **cfg_kw)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    p = nn.deploy_transformer_cim(jax.random.PRNGKey(7), params, cfg,
                                  mode="ideal",
                                  mesh_shape={"model": n_shards})
    return cfg, params, p


def test_mesh_width_mismatch_falls_back_to_loop():
    """A chip stack deployed wider than the mesh's 'model' axis raises,
    naming both widths: a stack deployed for another mesh must not serve
    silently from one device. Without a mesh the same stack serves through
    the loop (the executor `--cim-mesh off` asks for)."""
    import repro.models.nn as nn
    from repro.launch.mesh import make_mesh
    cfg, params, p = _dense_deploy(2)
    spl = p["layers"]["wq_cim"]
    spl0 = nn.ShardedPackedLayer(
        jax.tree_util.tree_map(lambda a: a[0], spl.shards),
        spl.partition, spl.n_shards)
    mesh = make_mesh((1, 1), ("data", "model"))   # model=1 != 2 shards
    ccfg = nn.arch_cim_config(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, cfg.d_model))
    y_none = nn.sharded_packed_forward(spl0, x, ccfg)
    np.testing.assert_array_equal(
        np.asarray(y_none), np.asarray(nn.sharded_packed_loop(spl0, x, ccfg)))
    with pytest.raises(ValueError, match="2 'model' shards.*1 devices"):
        nn.sharded_packed_forward(spl0, x, ccfg, mesh=mesh)


def test_mesh_and_mesh_shape_width_disagreement_raises():
    """An explicit mesh_shape whose 'model' width disagrees with the
    supplied mesh raises up front — not as an opaque device_put
    divisibility error inside placement."""
    import repro.models.nn as nn
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="disagrees with the serving"):
        nn._resolve_mesh(object(), mesh, {"model": 2})
    # agreeing shapes pass through
    m, ms = nn._resolve_mesh(object(), mesh, {"model": 1})
    assert m is mesh and ms["model"] == 1


def test_in_alpha_unknown_name_raises():
    """Satellite: a per-name in_alpha dict with an unknown projection name
    must raise instead of being silently ignored (the typo'd entry would
    deploy its target at the 1.0 default clip)."""
    import repro.models.nn as nn
    from repro.core.types import CIMConfig
    ccfg = CIMConfig(in_bits=4, out_bits=8)
    w = {"wq": 0.1 * jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))}
    with pytest.raises(ValueError, match="wq_typo"):
        nn.deploy_packed_stack(jax.random.PRNGKey(1), w, ccfg, mode="ideal",
                               in_alpha={"wq_typo": 2.0})
    # valid keys (including a strict subset) still deploy
    out = nn.deploy_packed_stack(jax.random.PRNGKey(1), w, ccfg,
                                 mode="ideal", in_alpha={"wq": 2.0})
    assert "wq" in out


def test_in_alpha_unknown_name_raises_through_sharded_deploy():
    """The same validation holds through _deploy_sharded_stacks, whose
    sharded/replicated deploy groups each see only a SUBSET of the names
    (a valid full-stack dict must not trip the per-group check)."""
    import repro.models.nn as nn
    from repro.core.types import CIMConfig
    ccfg = CIMConfig(in_bits=4, out_bits=8)
    stacked = {
        "wq": 0.1 * jax.random.normal(jax.random.PRNGKey(0), (1, 64, 32)),
        "wo": 0.1 * jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64)),
        # 8-indivisible: lands in the replicated 'none' deploy group
        "w_g": 0.1 * jax.random.normal(jax.random.PRNGKey(2), (1, 64, 31)),
    }
    alphas = {"wq": 2.0, "wo": 3.0, "w_g": 1.5}
    out = nn._deploy_sharded_stacks(
        jax.random.PRNGKey(3), stacked, ccfg, mode="ideal",
        in_alpha=alphas, mesh_shape={"model": 2}, spec=None)
    assert out["wq"].partition == "col" and out["w_g"].partition == "none"
    with pytest.raises(ValueError, match="nope"):
        nn._deploy_sharded_stacks(
            jax.random.PRNGKey(3), stacked, ccfg, mode="ideal",
            in_alpha=dict(alphas, nope=9.0), mesh_shape={"model": 2},
            spec=None)


# --------------------------------------------------- 8-device parity child

def test_shard_map_parity_8_devices():
    """Bitwise parity of the shard_map executor against the unrolled-loop
    oracle on a real 8-device mesh — col/row/none partitions, multi-pass
    scheduled plans, IR-drop split plans, MoE expert-parallel dispatch,
    whole 2-layer prefill + decode steps reading scanned tile stacks, one
    kernel trace per plan, deploy-time device placement."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "_mesh_parity_child.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    d = json.loads(proc.stdout.strip().splitlines()[-1])
    assert d["device_count"] == 8
    assert d["mesh_shape"] == {"data": 1, "model": 8}

    plain = d["plain"]
    assert plain["wq"]["partition"] == "col"
    assert plain["wo"]["partition"] == "row"
    assert plain["w_g"]["partition"] == "none"      # d_ff=255: indivisible
    # the merged-core variant actually runs multi-pass scheduled plans
    assert any(r["n_passes"] > 1 for r in d["sched"].values())
    for tag in ("plain", "sched", "irdrop"):
        for name, r in d[tag].items():
            assert r["bitwise"], (tag, name, r)
            assert r["deterministic"], (tag, name, r)
            # one shard_map body trace per plan shape; the kernel jit
            # cache is process-global, so a same-shape hit may cost 0
            assert r["mesh_traces_first"] <= 1, (tag, name, r)
            assert r["mesh_traces_repeat"] == 0, (tag, name, r)
            if r["n_shards"] > 1:
                assert r["placed"], (tag, name, r)   # device-resident
            if r["partition"] == "row":
                # the lax.psum lowering works (close, not bitwise)
                assert r["psum_close"], (tag, name, r)
    assert d["moe"]["bitwise"] and d["moe"]["placed"]
    for tag in ("step_dense", "step_moe"):
        # the mesh run's decode step runs its projections under shard_map
        assert d[tag]["mesh_shard_maps"] > 0, (tag, d[tag])
        assert d[tag]["bitwise"] and d[tag]["finite"], (tag, d[tag])

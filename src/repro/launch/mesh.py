"""Production meshes. Functions (not module-level constants) so that
importing never touches jax device state — dryrun.py sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.

`make_mesh` is the one place a Mesh is constructed; every other mesh
function here and in launch/distributed goes through it."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """A Mesh of `shape` over `devices` (default: all devices) with AUTO
    axis types. jax.make_mesh defaults to Explicit axes, under which every
    serving jit must carry sharding-typed avals — the KV-cache
    dynamic_update_slice then raises ShardingTypeError. The serving and
    training steps rely on GSPMD propagation, i.e. Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model); the pod axis composes
    as an outer data-parallel axis (gradient all-reduce crosses the slower
    inter-pod links — kept to one collective per step)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def mesh_shape_for(n: int, max_model: int = 16) -> dict:
    """{'data': D, 'model': M} factoring of an arbitrary device count —
    the rule itself, detached from any jax device query so the
    multi-process layer (launch/distributed.serving_mesh) can apply it to
    a per-process LOCAL device count while this module keeps applying it
    to the global one.

    Factoring rule (explicit, because it is easy to read past): the model
    axis takes the LARGEST POWER OF TWO that divides the device count,
    capped at `max_model` (the production mesh's TP width); everything
    else — every odd factor included — lands on the data axis. So 8
    devices factor as {'data': 1, 'model': 8}, 12 as {'data': 3,
    'model': 4}, 6 as {'data': 3, 'model': 2}, and a fully odd count
    (3, 5, 7 devices) yields {'data': n, 'model': 1}: an odd factor
    structure silently degrades to pure data parallelism. That is
    deliberate — per-shard chip plans require the projection dims (powers
    of two in every assigned arch) to divide the TP width — but callers
    who need TP must check `['model'] > 1`. A 1-device dev box yields
    {'data': 1, 'model': 1}."""
    m = 1
    while m * 2 <= min(n, max_model) and n % (m * 2) == 0:
        m *= 2
    return {"data": n // m, "model": m}


def serving_mesh_shape(max_model: int = 16) -> dict:
    """`mesh_shape_for` over the ACTUAL device count — what the serving
    driver hands to per-shard deployments (one CIM engine per TP shard,
    models/nn.deploy_transformer_cim) instead of a hardcoded {'model': 1}.
    Single-process only: `jax.device_count()` counts EVERY process's
    devices, so under `jax.distributed` a per-process mesh must come from
    launch/distributed.serving_mesh (local devices) instead."""
    return mesh_shape_for(jax.device_count(), max_model)


def serving_mesh(max_model: int = 16):
    """The ACTUAL serving `Mesh` over the local devices, axes
    ('data', 'model'), shaped by `serving_mesh_shape`'s factoring rule —
    the one mesh builder `launch/serve.py` and the shard_map TP executor
    (`models/nn.sharded_packed_forward`) share, so the driver stops
    rebuilding it inline. Per-shard packed engines are placed onto it at
    deploy time (`models/nn.deploy_transformer_cim(mesh=...)`): each
    'model'-axis device holds its own shard's compiled chip stack and the
    packed Pallas dispatch runs device-resident under `shard_map`, with
    exactly one collective per projection (psum for row-parallel partial
    sums, the out-spec all-gather for column-parallel slices)."""
    shape = serving_mesh_shape(max_model)
    return make_mesh((shape["data"], shape["model"]), ("data", "model"))

"""Mesh construction helpers.

The reference manages machine lists, ports and socket meshes
(ref: src/network/linkers_socket.cpp:81-189); on TPU the topology is XLA's
problem — we just name axes on a device mesh (jax-ml.github.io/scaling-book
recipe: pick a mesh, annotate shardings, let XLA insert collectives).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import log, platform

DATA_AXIS = "data"
FEATURE_AXIS = "feature"


def shard_map(f, mesh: Mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the replication check off by default (the
    growers' psum'd outputs are replicated by construction). Every
    shard_map in the tree learners routes through here."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def donate_argnums(*argnums: int):
    """``donate_argnums`` tuple for jax.jit, empty in the CPU test mode
    (XLA:CPU copies anyway and warns per lowering) — the one-line idiom
    every driver jit that re-writes its score/gradient carry buffers
    routes through (boosting/gbdt.py fast path, megastep, valid
    updates, parallel growers; ingest/prefetch.py). Donation
    composes with sharded operands: a row-sharded score matrix donates
    per-shard buffers, so the in-place update holds on every device."""
    return tuple(argnums) if platform.on_tpu() else ()


def make_mesh(n_devices: Optional[int] = None,
              axis_name: str = DATA_AXIS,
              devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over available devices (the data-parallel default).

    Multi-host: call after jax.distributed.initialize(); jax.devices()
    spans the pod slice and the same code shards over ICI+DCN.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            log.fatal("Requested %d devices but only %d available "
                      "(set XLA_FLAGS=--xla_force_host_platform_device_count "
                      "for virtual CPU devices)", n_devices, len(devices))
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def make_mesh_2d(n_data: int, n_feature: int) -> Mesh:
    """2-D mesh for combined data × feature sharding."""
    devices = jax.devices()
    need = n_data * n_feature
    if len(devices) < need:
        log.fatal("Requested %dx%d mesh but only %d devices", n_data,
                  n_feature, len(devices))
    arr = np.asarray(devices[:need]).reshape(n_data, n_feature)
    return Mesh(arr, (DATA_AXIS, FEATURE_AXIS))


def shard_rows(mesh: Mesh, array, axis_name: str = DATA_AXIS,
               pad_value=0):
    """Place a host array row-sharded on the mesh, padding rows to a multiple
    of the shard count (the pad rows carry zero weight downstream)."""
    n = array.shape[0]
    d = mesh.shape[axis_name]
    rem = (-n) % d
    if rem:
        pad_width = [(0, rem)] + [(0, 0)] * (array.ndim - 1)
        array = np.pad(np.asarray(array), pad_width,
                       constant_values=pad_value)
    spec = P(axis_name, *([None] * (array.ndim - 1)))
    return jax.device_put(array, NamedSharding(mesh, spec))


def replicate(mesh: Mesh, array):
    return jax.device_put(array, NamedSharding(mesh, P()))

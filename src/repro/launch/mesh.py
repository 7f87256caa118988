"""Production meshes.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis crosses DCN; the client (federated) axis spans pod x data.

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

from typing import Tuple

import jax


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``. The default axis type
    is ``Explicit``, under which indexing a bank leaf sharded over
    "data" with a cohort index array raises instead of letting XLA
    insert the cross-shard gather."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs (same axis names)."""
    return _mesh((1, 1), ("data", "model"))


def make_bank_mesh():
    """Mesh for the sharded client bank (``core.bank``): every local
    device on the client ("data") axis — bank leaves are per-client CNN
    blocks with no tensor-parallel dim to feed "model". Standard axis
    names, so ``client_axes``/``bank_sharding`` work on this mesh and on
    the production meshes alike."""
    return _mesh((len(jax.devices()), 1), ("data", "model"))


def client_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that jointly form the federated-client axis."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def n_client_shards(mesh) -> int:
    n = 1
    for a in client_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]

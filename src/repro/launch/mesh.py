"""Production mesh construction.

Kept as FUNCTIONS (not module constants) so importing this module never
touches jax device state; dryrun.py sets XLA_FLAGS before calling.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh
from repro.sharding.specs import MeshSpec


def _auto_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Target: TPU v5e pods — 16×16 (256 chips) per pod; 2 pods = 512 chips.

    Axes: "pod" (slow DCI hop), "data" (DP/FSDP), "model" (TP/EP/SP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_spec(*, multi_pod: bool = False) -> MeshSpec:
    return MeshSpec(make_production_mesh(multi_pod=multi_pod))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _auto_mesh((data, model), ("data", "model"))


def make_shard_mesh(shards: int, axis: str = "shard") -> Mesh:
    """1-D mesh for the sharded admission datapath (many ingress hosts
    feeding one fleet — ops.admit_commit_sharded, DESIGN.md §7).

    Needs ``shards`` addressable devices; off-hardware runs get them via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before jax
    initializes (cf. tests/test_distributed.py)."""
    n = len(jax.devices())
    if shards > n:
        raise RuntimeError(
            f"{shards}-way admission sharding needs {shards} devices, "
            f"found {n}; off-TPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{shards} before jax initializes")
    return _auto_mesh((shards,), (axis,))

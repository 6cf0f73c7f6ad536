"""Production mesh construction + sharding-spec sanitation.

`make_production_mesh` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  Single pod: (data=16,
model=16) = 256 chips of TPU v5e.  Multi-pod: (pod=2, data=16, model=16) =
512 chips; the 'pod' axis joins data parallelism (gradient all-reduce
crosses pods over DCN/optical links; FSDP weight gathering stays intra-pod
by construction — ZeRO shards only over 'data').
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh() -> Mesh:
    """1x1 mesh over however many local devices exist (tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def _axes_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def sanitize_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes from dims they don't divide evenly.

    Keeps lowering robust for awkward dims (e.g. granite's vocab 49155 on a
    16-way model axis) — the dim falls back to replication and the fact is
    visible in the dry-run report (bytes/device goes up).
    """
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape)
                                                          - len(spec))):
        if entry is not None and dim % _axes_size(mesh, entry) != 0:
            entry = None
        out.append(entry)
    return P(*out)


def to_named(tree_specs: Any, tree_shapes: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree (+ matching ShapeDtypeStruct tree) -> NamedSharding
    tree, with divisibility sanitation."""
    def conv(spec, sds):
        return NamedSharding(mesh, sanitize_spec(spec, sds.shape, mesh))
    return jax.tree.map(conv, tree_specs, tree_shapes,
                        is_leaf=lambda x: isinstance(x, P))

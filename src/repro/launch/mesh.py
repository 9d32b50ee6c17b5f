"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (tests see one CPU device; only launch/dryrun.py
sets the 512-placeholder-device XLA flag before first jax init).

Topology: TPU v5e pods of 16x16 = 256 chips.  Single pod: (data=16,
model=16) — ICI on both axes.  Multi-pod: leading `pod` axis (size 2 here;
scales to N pods) mapped over DCN, used for data parallelism with optional
gradient compression (distributed/collectives.py).

Multi-replica serving adds a leading ``replica`` axis: each index along
it is one full serving cell — an independent SpinEngine whose LLM is
sharded over that slice's remaining (data, model) axes.  The replica
axis carries NO collectives (replicas never communicate; the router in
serving/router.py balances the request stream between them), so it maps
over DCN for free.  ``replica_submeshes`` carves the per-replica
sub-meshes; the existing rule tables in distributed/sharding.py apply
unchanged because the replica axis never appears inside a sub-mesh.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import numpy as np
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes: the rule tables place arrays
    through ``with_sharding_constraint`` (distributed/sharding.py), which
    accepts only ``Auto`` axes, while ``make_mesh`` defaults to
    ``Explicit`` ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, replicas: int = 1):
    shape: Tuple[int, ...] = (2, 16, 16) if multi_pod else (16, 16)
    axes: Tuple[str, ...] = (("pod", "data", "model") if multi_pod
                             else ("data", "model"))
    if replicas > 1:
        shape = (replicas,) + shape
        axes = ("replica",) + axes
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, replicas: int = 1):
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    if replicas > 1:
        return _auto_mesh((replicas, data, model),
                          ("replica", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def carve_replica_axis(devices: np.ndarray, axis_names: Tuple[str, ...]
                       ) -> Tuple[List[np.ndarray], Tuple[str, ...]]:
    """Split a mesh's device array along its ``replica`` axis: one device
    sub-array per replica, plus the axis names that remain.  Pure array
    logic (unit-testable without multi-device jax); without a replica
    axis the whole array is the single replica's."""
    if "replica" not in axis_names:
        return [devices], tuple(axis_names)
    ax = list(axis_names).index("replica")
    moved = np.moveaxis(np.asarray(devices), ax, 0)
    names = tuple(n for n in axis_names if n != "replica")
    return [moved[i] for i in range(moved.shape[0])], names


def replica_submeshes(mesh) -> List[jax.sharding.Mesh]:
    """One sub-mesh per index of the mesh's ``replica`` axis (the whole
    mesh if it has none).  Each sub-mesh keeps the remaining axes, so
    serve/train rule tables resolve against it exactly as on a
    single-replica mesh — replicas are full parameter copies, data
    parallel over the replica axis by construction."""
    parts, names = carve_replica_axis(np.asarray(mesh.devices),
                                      tuple(mesh.axis_names))
    if len(parts) == 1 and "replica" not in mesh.axis_names:
        return [mesh]
    return [jax.sharding.Mesh(p, names) for p in parts]


def elastic_replica_submeshes(mesh, replicas_max: int
                              ) -> List[jax.sharding.Mesh]:
    """Pre-carve the MAXIMUM fleet's sub-meshes for the elastic router.

    Device meshes cannot be re-carved while engines hold sharded arrays
    on them, so autoscaling provisions capacity the same way real fleets
    do: the full ``replicas_max`` device slice is reserved up front, one
    sub-mesh (and one standby engine) per slot, and the router's
    lifecycle states — not the mesh — decide which slots are serving.
    The *provisioning ledger* (FleetStats.provisioned_s) then charges
    only active sim-seconds, the honest cost an operator who can
    release idle slices back to the pool would pay.

    The mesh's replica axis must carry exactly ``replicas_max`` slots —
    a mismatch means the launch carved a different fleet than the
    router was configured for, which would mispair engines and device
    slices silently."""
    if replicas_max < 1:
        raise ValueError("replicas_max must be >= 1")
    subs = replica_submeshes(mesh)
    if len(subs) != replicas_max:
        raise ValueError(
            f"mesh carves {len(subs)} replica sub-meshes but the elastic "
            f"fleet needs replicas_max={replicas_max} — launch with "
            f"--replicas equal to --replicas-max")
    return subs

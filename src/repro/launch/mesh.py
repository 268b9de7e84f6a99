"""Production meshes (functions, not module constants — importing this
module never touches jax device state).

Every mesh here has *Auto* axes: the partitioner propagates shardings from
the ``sharding.rules`` annotations, which is what those rules were written
for.  (``jax.make_mesh`` defaults to Explicit axes, under which a gather or
a ``pallas_call`` must carry its sharding in its type.)  Pallas kernels,
which no partitioner can split, run per shard under ``jax.shard_map`` in
the op layer (``repro.sharding.local.kernel_mesh``).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Arbitrary mesh with Auto axes, e.g. (2, 2) x (data, model).

    ``devices`` defaults to the visible devices; pass described devices
    (``jax.experimental.topologies``) to compile for a chip that is not
    attached."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_devices: int | None = None):
    """A smoke-scale (data, model) mesh over the host's visible devices.

    Used by the dry-run blocks smoke and tests running under
    ``--xla_force_host_platform_device_count=N``: the model axis takes the
    largest power-of-two factor up to 16 that still leaves a data axis
    (e.g. 8 devices -> (2, 4)), mirroring the production mesh's shape
    hierarchy at host scale.
    """
    n = n_devices or jax.device_count()
    model = 1
    while model * 2 <= min(n // 2, 16) and n % (model * 2) == 0:
        model *= 2
    return make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """Data-parallel / FSDP axes present in the mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axes(mesh):
        s *= mesh.shape[a]
    return s


def model_size(mesh) -> int:
    return mesh.shape.get("model", 1)

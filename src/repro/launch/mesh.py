"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — dryrun.py must set
XLA_FLAGS=--xla_force_host_platform_device_count BEFORE first jax use.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape: tuple[int, ...], names: tuple[str, ...]):
    """A mesh whose axes are all ``Auto``: the partitioner propagates
    shardings from the ``PartitionSpec``s the layers declare.  (JAX's
    ``make_mesh`` now defaults to ``Explicit`` axes, under which every
    gather or contraction over a sharded axis must state its output
    sharding; the layers are written for propagation.)"""
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names))


def production_mesh_shape(*, multi_pod: bool = False
                          ) -> tuple[tuple[str, int], ...]:
    """(axis, size) pairs of the production mesh, importable WITHOUT
    touching jax device state — consumers that only need the topology
    (the fleet workload extractor sizing per-device shards) use this
    instead of materializing a device mesh."""
    if multi_pod:
        return (("pod", 2), ("data", 16), ("model", 16))
    return (("data", 16), ("model", 16))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    axes_sizes = production_mesh_shape(multi_pod=multi_pod)
    return auto_mesh(tuple(s for _, s in axes_sizes),
                     tuple(a for a, _ in axes_sizes))


def make_debug_mesh(devices: int | None = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = devices or len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return auto_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes that carry data parallelism."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def dp_size(mesh) -> int:
    import math
    return math.prod(mesh.shape[a] for a in dp_axes(mesh))

"""Production meshes.

Functions (not module constants) so importing never touches jax device
state -- the dry-run forces 512 host devices before calling these."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: 16x16 (data, model).  Multi-pod: 2x16x16
    (pod, data, model) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh(devices: int | None = None) -> jax.sharding.Mesh:
    """Small (data, model) mesh over the first ``devices`` devices
    (all of them by default), with auto-sharded axes (see
    :func:`auto_axes`)."""
    n = devices or len(jax.devices())
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0:
            model = cand
            break
    return auto_axes(jax.make_mesh((n // model, model), ("data", "model"),
                                   devices=jax.devices()[:n]))


def auto_axes(mesh: jax.sharding.Mesh) -> jax.sharding.Mesh:
    """The same devices and axis names with every axis ``Auto``.

    The solver's mesh paths run their per-iteration work inside
    ``shard_map`` and leave everything around it -- lane writes,
    admission scatters, result indexing -- to the compiler's sharding
    propagation.  ``jax.make_mesh`` makes ``Explicit`` axes, under which
    each of those scatters and gathers on a sharded axis needs its own
    ``out_sharding``; callers that accept a user's mesh normalize it
    here instead."""
    auto = jax.sharding.AxisType.Auto
    if all(t == auto for t in mesh.axis_types):
        return mesh
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names,
                             axis_types=(auto,) * len(mesh.axis_names))

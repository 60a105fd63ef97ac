"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: 16x16 = 256 chips (v5e pod), axes
("data", "model").  Multi-pod: 2x16x16 = 512 chips, axes ("pod", "data",
"model") — the "pod" axis carries data parallelism across pods (its
collectives traverse DCN, which is why gradient compression targets it
first).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kwargs(n_axes: int) -> dict:
    """Mesh kwargs asking for Auto axis types."""
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = 1
    for s in shape:
        ndev *= s
    devices = jax.devices()[:ndev]
    if len(devices) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for mesh {shape}; have {len(devices)} "
            "(the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512)")
    import numpy as np
    dev_array = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes, **_axis_kwargs(len(axes)))


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Small mesh over however many host devices exist (tests / examples)."""
    import numpy as np
    ndev = int(np.prod(shape))
    dev = np.asarray(jax.devices()[:ndev]).reshape(shape)
    return jax.sharding.Mesh(dev, axes, **_axis_kwargs(len(axes)))


def serving_mesh(n_shards: int, axis: str = "shards"):
    """1-D mesh for doc-range sharded serving: one device per shard, or None
    when the backend has fewer devices than shards (the engine then runs the
    shards logically on one device — same results, no placement)."""
    if n_shards < 1 or len(jax.devices()) < n_shards:
        return None
    return make_host_mesh((n_shards,), (axis,))

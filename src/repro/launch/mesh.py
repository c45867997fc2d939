"""Production mesh construction (DESIGN.md §4, §2.5).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax import;
tests and benchmarks see the real single device.

Every mesh here is Auto-typed (``jax.sharding.AxisType.Auto``), which is
what the sharded index executor (``repro.index.shard``) and the
multi-device tests under ``--xla_force_host_platform_device_count`` need.
"""

from __future__ import annotations

import numpy as np

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices tests have."""
    return _mk((data, model), ("data", "model"))


def make_index_mesh(n_devices: int | None = None):
    """1-D ('data',) mesh for sharded index serving (DESIGN.md §2.5/§2.9).

    Index parts shard along 'data' only — there is no model axis in the
    query engine — so this is a plain ``Mesh`` over the first ``n_devices``
    local devices (all of them by default).  Uses the raw Mesh constructor,
    not ``jax.make_mesh``, so the device order is exactly ``jax.devices()``
    order: the shard→device placement map stays the identity and is easy to
    audit (``ShardedIndex.placement``)."""
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    assert 1 <= n_devices <= len(devs), (n_devices, len(devs))
    return jax.sharding.Mesh(np.array(devs[:n_devices]), ("data",))

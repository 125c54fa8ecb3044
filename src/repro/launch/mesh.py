"""Production meshes.  A FUNCTION, not a module constant: importing this
module must never touch jax device state (the dry-run sets the fake device
count in its main, before JAX's backend first initializes).
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 ("data","model") = 256 chips.
    Multi-pod: 2x16x16 ("pod","data","model") = 512 chips (2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Tiny mesh over forced host devices — used by reduced-scale dry-run
    tests (8 fake devices) so CI exercises the same code path."""
    return _make_mesh(shape, axes)


def make_expert_mesh(n_experts: int, n_devices: int | None = None):
    """1-D ``("model",)`` mesh for expert-parallel dispatch
    (``moe_dispatch="mesh-ws"``): the model axis spans the largest divisor
    of ``n_experts`` that the host's device count allows, so the expert
    partition is always even.  One device degenerates to a 1-mesh (the
    mesh_ws code path with no remote victims).  Pass ``n_devices`` to pin
    the size (it must divide ``n_experts`` and be available)."""
    avail = len(jax.devices())
    if n_devices is None:
        n_devices = max(
            d for d in range(1, min(avail, n_experts) + 1)
            if n_experts % d == 0
        )
    if n_devices > avail:
        raise ValueError(f"mesh size {n_devices} > {avail} available devices")
    if n_experts % n_devices:
        raise ValueError(
            f"mesh size {n_devices} does not divide n_experts={n_experts}"
        )
    return _make_mesh((n_devices,), ("model",))

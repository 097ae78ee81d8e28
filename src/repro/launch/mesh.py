"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before any jax call).

``make_host_mesh`` is the elastic entry point: ``exclude`` drops lost
devices (by ``Device.id``) and rebuilds the largest usable mesh over the
survivors — the degraded-mesh recovery path in ``repro.launch.elastic``.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes: its default is ``Explicit``,
    under which the engine's traced indexing of sharded arrays is refused."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256 chips/pod) single-pod or 2x16x16 multi-pod mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model"), *, exclude=()):
    """Small mesh over whatever devices exist (tests / CPU dry-runs).

    ``exclude`` names lost devices by ``Device.id``; the mesh is rebuilt
    over the survivors. With no explicit ``shape`` the survivors split as
    (n//2, 2) when n is even, else (n, 1) — worker groups (the ``data``
    axis) are preserved over inner parallelism so a degraded mesh keeps
    as many competitive searchers as possible.
    """
    lost = frozenset(exclude)
    devs = [d for d in jax.devices() if d.id not in lost]
    if not devs:
        raise RuntimeError(
            f"no devices survive exclusion of {sorted(lost)}"
        )
    n = len(devs)
    if shape is None:
        model = 2 if n > 1 and n % 2 == 0 else 1
        shape = (n // model, model)
    need = math.prod(shape)
    if need > n:
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {need} devices, "
            f"only {n} survive"
        )
    return _make_mesh(shape, axes, devices=devs[:need])

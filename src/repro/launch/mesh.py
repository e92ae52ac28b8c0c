"""Production mesh definitions.

Every builder here is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state — required because the
dry-run must set ``XLA_FLAGS`` *before* the first jax device query, and
smoke tests must keep seeing 1 device.

Meshes (assignment):
  single-pod:  (16, 16)      axes ("data", "model")   = 256 chips
  multi-pod:   (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

``alt_mesh`` builds §Perf-lever variants (e.g. (32, 8) to restore attention
TP for 40/24/20-head archs) — same chip count, different axis split.

``make_chains_mesh`` is the sampler engine's scale-out mesh: a 1-D
process-spanning device mesh for the "chains" sharding rule (DESIGN.md
§Chains-axis / §Run-API).  CI exercises it at N host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(tests/test_multidevice.py).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def alt_mesh(data: int, model: int, *, pods: int = 1):
    """Same-chip-count §Perf variants, e.g. alt_mesh(32, 8)."""
    if pods > 1:
        return jax.make_mesh(
            (pods, data, model),
            ("pod", "data", "model"),
            axis_types=(AxisType.Auto,) * 3,
        )
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def make_chains_mesh(num_chains: int | None = None, *, devices=None):
    """The engine's scale-out mesh: 1-D ("data",) over every addressable
    device, for sharding the chains axis via the "chains" rule.

    ``jax.devices()`` spans *all* processes in a multi-host run, so the
    same call builds the process-spanning production mesh and the
    CI-side mock (``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    turns one CPU into N host devices).  Returns ``None`` when sharding
    cannot help — fewer than 2 devices, or a known chain count below 2 —
    so callers can pass the result straight to ``RunPlan(mesh=...)``.
    """
    if num_chains is not None and num_chains < 2:
        return None
    if devices is None:
        devices = jax.devices()
    if len(devices) < 2:
        return None
    return jax.sharding.Mesh(np.asarray(devices), ("data",))


def mesh_chip_count(mesh) -> int:
    n = 1
    for s in mesh.axis_sizes:
        n *= s
    return n

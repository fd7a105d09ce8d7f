"""Mesh construction.

``device_mesh`` is the one mesh rule for serving, training and their smoke
runs: a ``(data=1, model=n)`` mesh over the devices present, with the
logical SHMEM PE grid derived from ``n`` by :func:`plan_for_mesh` — 1x1 on
one chip, 2x2 on a four-chip host, 4x4 on a 16-device host-CPU mesh.  The
model axis is flat; the SHMEM library treats it as a logical q x r PE grid
by index arithmetic (repro.core.shmem), exactly as OpenSHMEM programs treat
their flat PE space.

``make_production_mesh`` describes the 256/512-chip layout the CPU dry run
(``repro.launch.dryrun``) lowers against.  Importing this module never
touches jax device state.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from repro.partition import DATA, MODEL, MeshPlan, plan_for_mesh


def device_mesh(devices: Optional[Sequence] = None) -> Tuple[Mesh, MeshPlan]:
    """``(data=1, model=len(devices))`` mesh and its grid plan; default
    ``jax.devices()``."""
    devs = list(jax.devices() if devices is None else devices)
    mesh = Mesh(np.asarray(devs).reshape(1, len(devs)), (DATA, MODEL))
    return mesh, plan_for_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) != n:
        assert len(devices) >= n, (
            f"need {n} devices, have {len(devices)} — the dry-run entrypoint "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import")
        return Mesh(np.array(devices[:n]).reshape(shape), axes)
    return jax.make_mesh(shape, axes)


def production_plan(mesh, pp_stages: int = 1) -> MeshPlan:
    return plan_for_mesh(mesh, pp_stages=pp_stages)

"""Picklable engine recipe: rebuild the SAME ServingEngine in a fresh process.

A :class:`jax.sharding.Mesh` holds live device objects and cannot cross a
process boundary; model parameters could, but shipping them through a pipe
would dwarf every other supervisor cost.  So the replica worker receives
neither — it receives this recipe and rebuilds both: the mesh from the
:class:`~repro.partition.MeshPlan`'s axis names/sizes, or, with no plan,
from the devices the worker finds (:func:`repro.launch.mesh.device_mesh`,
so a parent that never touches a device can still describe the replica),
and the parameters from the deterministic seed-keyed initializer.  Two
processes building from the same spec therefore hold bit-identical engines — the property the supervisor's token-for-token
failover parity stands on.

Everything referenced here must survive ``pickle`` under the
``multiprocessing`` *spawn* start method (fork is unsafe once the parent
has initialized JAX): :class:`~repro.models.config.ModelConfig`,
:class:`~repro.partition.MeshPlan` and
:class:`~repro.serve.engine.engine.EngineConfig` are plain dataclasses;
an :class:`~repro.serve.resilience.faults.FaultInjector` inside the engine
config pickles with its seed and rng state, so every worker incarnation
starts an identical fault schedule.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.partition import MeshPlan
from repro.serve.engine.engine import EngineConfig, ServingEngine


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """Everything a fresh process needs to rebuild one serving replica."""

    model_cfg: object                 # repro.models.config.ModelConfig
    plan: Optional[MeshPlan]          # None: the mesh rule over the
    #                                   worker's own devices
    engine_cfg: EngineConfig
    seed: int = 0                     # params are a pure function of this

    def make_mesh(self):
        """Rebuild ``(mesh, plan)`` over this process's local devices."""
        import jax

        from repro.launch.mesh import device_mesh
        if self.plan is None:
            return device_mesh()
        return jax.make_mesh(
            self.plan.axis_sizes, self.plan.axis_names,
            axis_types=(jax.sharding.AxisType.Auto,)
            * len(self.plan.axis_names)), self.plan

    def build(self) -> ServingEngine:
        """Construct the engine — params initialized from ``seed``, so
        every incarnation built from this spec is parameter-identical."""
        from repro.serve.engine.api import build_engine
        mesh, plan = self.make_mesh()
        return build_engine(self.model_cfg, mesh, plan,
                            engine_cfg=self.engine_cfg, seed=self.seed)

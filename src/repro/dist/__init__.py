"""Distributed substrate: logical-axis sharding over jax meshes.

``dist.constrain(x, ("dp", None, "tp"))`` is the whole model-side API —
logical axes resolve against whatever mesh is active (see
:mod:`repro.dist.api`) and every op is a no-op off-mesh, so the same model
code runs on a CPU test, a single host, or a multi-pod production mesh.
:mod:`repro.dist.sharding` holds the path-based parameter/optimizer/
batch/cache placement rules used by the launchers and the serving engine.
"""
from repro.dist import api, placement, sharding            # noqa: F401
from repro.dist.api import (active_mesh, constrain,        # noqa: F401
                            constrain_heads, dp_size, logical_to_mesh,
                            manual_mode, mesh_axes_for, tp_size,
                            use_mesh)
from repro.dist.placement import (PlacementPlan,           # noqa: F401
                                  plan_for_controller, plan_placement)

"""Production mesh construction — DEPRECATED shims over
:mod:`repro.parallel.plan`.

``make_production_mesh`` predates the ParallelPlan API: it hard-coded the
two production layouts and left the rule table, pipeline staging and
fabric model to be hand-threaded by every caller.  New code should use::

    from repro.parallel.plan import resolve_plan
    plan = resolve_plan("multi-pod")        # or "single-pod" / "auto" / file
    mesh = plan.mesh()

The shim stays a FUNCTION (never a module-level constant) so importing
this module does not touch jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds meshes (``repro.parallel.sharding.make_mesh``
is the one constructor).

Mesh semantics (DESIGN.md §2): ``pod`` is the paper's cross-pod spine hop
(2 pods × 8 leaf switches), ``data`` the intra-pod data-parallel/FSDP rail
group, ``model`` the tensor-parallel rail set within a node group.
"""
from __future__ import annotations

import warnings
import numpy as np
from jax.sharding import Mesh

from repro.parallel.plan import resolve_plan


def make_production_mesh(*, multi_pod: bool = False):
    warnings.warn(
        "make_production_mesh is deprecated; use repro.parallel.plan."
        "resolve_plan('multi-pod' | 'single-pod' | 'auto').mesh() — the "
        "plan carries the rule table and collective schedule with the mesh",
        DeprecationWarning, stacklevel=2)
    return resolve_plan("multi-pod" if multi_pod else "single-pod").mesh()


def mesh_chips(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))

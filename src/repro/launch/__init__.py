"""Launch layer: meshes, sharding policies, dry-run, roofline, CLI drivers.

NOTE: ``dryrun`` must be imported/executed as the process entry point (it
pins ``XLA_FLAGS`` before jax init); this package ``__init__`` therefore
does NOT import it.
"""

from . import mesh, policy, roofline
from .mesh import HW, hardware, make_host_mesh, make_production_mesh

__all__ = [
    "HW",
    "hardware",
    "make_host_mesh",
    "make_production_mesh",
    "mesh",
    "policy",
    "roofline",
]

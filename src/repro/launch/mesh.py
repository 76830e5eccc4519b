"""Production mesh construction (deliverable e).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets the 512-device
host platform before calling it.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh

from repro.core.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for_devices(n: int | None = None, *, model: int | None = None) -> Mesh:
    """Best-effort mesh on the actually-available devices (train/serve/smoke).

    Picks the largest model axis that divides the device count (capped at 16,
    the production TP width).
    """
    n = n or len(jax.devices())
    model = model or next(m for m in (16, 8, 4, 2, 1) if n % m == 0)
    return make_mesh((n // model, model), ("data", "model"))


def make_solver_mesh(n: int | None = None) -> Mesh:
    """1-D mesh for the paper-faithful HPCCG layout (z-only decomposition)."""
    n = n or len(jax.devices())
    return make_mesh((n,), ("cells",))


def make_solver_mesh_2d(n: int | None = None) -> Mesh:
    """Near-square ('data', 'model') mesh for the 2-D x/y block layout:
    2×2 on four chips, 4×2 on eight."""
    n = n or len(jax.devices())
    model = max(m for m in range(1, int(n ** 0.5) + 1) if n % m == 0)
    return make_mesh((n // model, model), ("data", "model"))

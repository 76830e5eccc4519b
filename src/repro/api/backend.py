"""Backend resolution: options + device topology -> how the solve executes.

One place owns the decision every driver used to make inline:

  * ``local``     — a single device; ``LocalOp`` with zero-padded halos.
  * ``shard_map`` — a device mesh; ``DistributedOp`` with ppermute halos and
                    psum reductions inside one shard_mapped program.

Resolution rules (documented in docs/API.md):

  1. An explicit ``mesh`` argument always wins; ``options.dims_map`` then
     overrides the default grid-dim -> mesh-axis mapping.
  2. ``layout="local"`` forces the single-device path.
  3. ``layout="auto"`` picks local on one device, else the paper-faithful
     1-D z decomposition over all devices.
  4. ``layout="1d" | "2d" | "3d"`` build the corresponding mesh over all
     devices (1-D ``cells`` / near-square data×model / pod×data×model).

The kernel choice is orthogonal: ``options.pallas`` swaps the local stencil
SpMV for the Pallas kernel in either world (``options.matvec_padded`` wins
over both).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
from jax.sharding import Mesh, NamedSharding

from repro.api.options import SolverOptions
from repro.core.compat import make_mesh
from repro.core.distributed import GridLayout, make_layout
from repro.core.operators import Stencil
from repro.launch.mesh import make_solver_mesh, make_solver_mesh_2d


@dataclasses.dataclass(frozen=True)
class Backend:
    """Resolved execution target for a solve."""

    kind: str                     # "local" | "shard_map"
    mesh: Mesh | None = None
    layout: GridLayout | None = None

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else self.mesh.devices.size

    def sharding(self) -> NamedSharding | None:
        if self.kind == "local":
            return None
        return NamedSharding(self.mesh, self.layout.spec())

    def describe(self) -> str:
        if self.kind == "local":
            return "local(1 device)"
        axes = ",".join(f"{a}={self.mesh.shape[a]}"
                        for a in self.mesh.axis_names)
        return f"shard_map({axes})"


def _mesh_3d(n: int) -> Mesh:
    """pod×data×model mesh over ``n`` devices (beyond-paper 3-D blocks)."""
    if n < 8:
        raise ValueError(f"3d layout needs >= 8 devices, have {n}")
    for model in (16, 8, 4, 2):
        if n % model == 0 and (n // model) % 2 == 0:
            return make_mesh((2, n // model // 2, model),
                             ("pod", "data", "model"))
    raise ValueError(f"cannot factor {n} devices into pod*data*model")


def resolve_backend(options: SolverOptions, *, mesh: Mesh | None = None,
                    n_devices: int | None = None) -> Backend:
    """Apply the resolution rules above.  ``n_devices`` is a test hook."""
    if mesh is not None:
        return Backend(kind="shard_map", mesh=mesh,
                       layout=make_layout(mesh, options.dims_map))
    n = n_devices if n_devices is not None else len(jax.devices())
    layout = options.layout
    if layout == "local" or (layout == "auto" and n == 1):
        return Backend(kind="local")
    if layout in ("auto", "1d"):
        mesh = make_solver_mesh(n)
    elif layout == "2d":
        mesh = make_solver_mesh_2d(n)
    else:  # "3d"
        mesh = _mesh_3d(n)
    return Backend(kind="shard_map", mesh=mesh,
                   layout=make_layout(mesh, options.dims_map))


def resolve_matvec(stencil: Stencil,
                   options: SolverOptions) -> Callable | None:
    """The padded-operand SpMV implementing ``options`` (None = jnp oracle)."""
    if options.matvec_padded is not None:
        return options.matvec_padded
    if options.pallas:
        from repro.kernels import ops
        return ops.make_matvec_padded(stencil)
    return None


def resolve_precond(options: SolverOptions):
    """Build the ``repro.precond.Preconditioner`` ``options`` asks for.

    ``None`` for ``precond="none"``.  ``options.pallas`` flows into the
    preconditioners that have fused Pallas kernels (``PALLAS_PRECONDS``)
    unless ``precond_params`` pins ``use_pallas`` explicitly — the same
    one-flag rule as the stencil SpMV.
    """
    if options.precond in (None, "none"):
        return None
    from repro.precond import PALLAS_PRECONDS, make_precond
    params = dict(options.precond_params or {})
    if options.pallas and options.precond in PALLAS_PRECONDS:
        params.setdefault("use_pallas", True)
    return make_precond(options.precond, **params)


def resolve_halo_mode(options: SolverOptions) -> str:
    """Resolve ``halo_mode="auto"`` for the distributed operator.

    ``"overlap"`` (interior/shell split, ppermutes hidden behind interior
    compute) is the default for the built-in stencil formulations — it is
    bit-for-bit identical to ``"concat"`` and strictly better on the
    schedule.  A user-supplied ``matvec_padded`` or the Pallas kernel may be
    tile-shape-specialised, so the slab-shaped shell applies fall back to
    the monolithic ``"concat"`` exchange there.
    """
    if options.halo_mode != "auto":
        return options.halo_mode
    if options.matvec_padded is not None or options.pallas:
        return "concat"
    return "overlap"

"""Distributed solver layer: the paper's MPI decomposition on a TPU mesh.

The paper decomposes the 3-D grid explicitly across MPI ranks (HPCCG splits
only the last dimension) and exchanges boundary planes point-to-point
(``exchange_externals``, Code 2).  Here the decomposition is expressed as a
``GridLayout`` mapping grid dims -> mesh axes, halos travel over
``lax.ppermute`` (nearest-neighbour ICI traffic), and global reductions are
``lax.psum``.  Everything runs inside one ``jax.shard_map``-wrapped solver so
the entire iteration is a single compiled program — the analogue of the
paper's zero-sequential-parts requirement (HDOT).

Faithful mode: 1-D decomposition of z over one flattened axis (the paper's
HPCCG layout).  Beyond-paper mode: full 3-D decomposition (x->model, y->data,
z->pod on the production mesh), which reduces halo bytes per device from
``2·nx·ny`` to the block's surface — see EXPERIMENTS.md §Perf.

Dimension-ordered halo exchange: each dim's slabs span the *padded* extent of
the other dims, so later exchanges forward previously received halos and the
27-pt stencil's edge/corner neighbours arrive correctly with only 6 ppermutes.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.methods import Ops, get_method, in_scope, local_dot, run_method
from repro.core.operators import (Stencil, interior_matvec, shell_assemble,
                                  shell_slabs, slab_operand)
from repro.core.problems import HPCGProblem
from repro.core.solvers import SolveResult

#: halo-exchange strategies of the distributed operator ("auto" resolves to
#: "concat" here; repro.api.backend upgrades it to "overlap" where safe)
HALO_MODES = ("auto", "scatter", "concat", "overlap")


@dataclasses.dataclass(frozen=True)
class GridLayout:
    """Maps grid dims (x, y, z) to mesh axis names (or None = not split)."""

    mesh: Mesh
    dim_axes: tuple[str | None, str | None, str | None]

    def __post_init__(self):
        for a in self.dim_axes:
            if a is not None and a not in self.mesh.axis_names:
                raise ValueError(f"axis {a!r} not in mesh {self.mesh.axis_names}")

    @property
    def reduce_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.dim_axes if a is not None)

    def spec(self) -> P:
        return P(*self.dim_axes)

    def axis_size(self, d: int) -> int:
        a = self.dim_axes[d]
        return 1 if a is None else self.mesh.shape[a]

    def local_shape(self, global_shape: tuple[int, int, int]) -> tuple[int, int, int]:
        out = []
        for d, g in enumerate(global_shape):
            n = self.axis_size(d)
            if g % n:
                raise ValueError(f"grid dim {d} ({g}) not divisible by mesh axis ({n})")
            out.append(g // n)
        return tuple(out)


class DistributedOp:
    """Stencil operator on a local block inside ``shard_map``.

    Protocol-compatible with ``solvers.LocalOp``: the solver code is identical
    in both worlds (the paper's write-once/parallelise-underneath goal).
    """

    def __init__(self, stencil: Stencil, layout: GridLayout,
                 matvec_padded: Callable | None = None,
                 halo_mode: str = "auto"):
        self.stencil = stencil
        self.layout = layout
        # the slice-add apply LocalOp runs, so one block's arithmetic is the
        # same on one chip and on a mesh
        self._mv_padded = matvec_padded or stencil.matvec_padded
        if not stencil.fuses_dot(matvec_padded):
            self.matvec_dot = None        # Ops.matvec_dot: matvec, then dot
        if halo_mode not in HALO_MODES:
            raise ValueError(
                f"unknown halo_mode {halo_mode!r}; options: {HALO_MODES}")
        if halo_mode == "auto":
            halo_mode = "concat"
        self.halo_mode = halo_mode

    @property
    def diag(self) -> float:
        return self.stencil.diag

    @property
    def split_dims(self) -> tuple[int, ...]:
        """Grid dims actually decomposed (mapped to a mesh axis of size > 1)."""
        return tuple(
            d for d, a in enumerate(self.layout.dim_axes)
            if a is not None and self.layout.mesh.shape[a] > 1)

    # --- halo exchange (the paper's exchange_externals) ----------------------
    def pad_exchange(self, x: jax.Array) -> jax.Array:
        if self.halo_mode == "scatter":
            return self._pad_exchange_scatter(x)
        return self._pad_exchange_concat(x)

    @in_scope("repro.halo")
    def _pad_exchange_scatter(self, x: jax.Array) -> jax.Array:
        """Baseline: zero-pad then scatter received planes into the halos.

        Costs a full-array pad copy plus per-dim ``.at[].set`` updates —
        measured at ~8r extra HBM traffic per matvec (EXPERIMENTS.md §Perf).
        """
        xp = jnp.pad(x, 1)
        for d, axis in enumerate(self.layout.dim_axes):
            if axis is None:
                continue
            n = self.layout.mesh.shape[axis]
            if n == 1:
                continue
            sl_lo = [slice(None)] * 3
            sl_hi = [slice(None)] * 3
            sl_lo[d] = slice(1, 2)        # my bottom interior plane
            sl_hi[d] = slice(-2, -1)      # my top interior plane
            up = lax.ppermute(                       # i -> i+1: fills my LOWER halo
                xp[tuple(sl_hi)], axis, [(i, i + 1) for i in range(n - 1)]
            )
            down = lax.ppermute(                     # i -> i-1: fills my UPPER halo
                xp[tuple(sl_lo)], axis, [(i + 1, i) for i in range(n - 1)]
            )
            halo_lo = [slice(None)] * 3
            halo_hi = [slice(None)] * 3
            halo_lo[d] = slice(0, 1)
            halo_hi[d] = slice(xp.shape[d] - 1, xp.shape[d])
            xp = xp.at[tuple(halo_lo)].set(up)
            xp = xp.at[tuple(halo_hi)].set(down)
        return xp

    @in_scope("repro.halo")
    def _pad_exchange_concat(self, x: jax.Array) -> jax.Array:
        """Optimised: build the padded array by per-dim concatenation.

        Unsplit dims take their zero halo from one ``jnp.pad``, as
        ``Stencil.matvec`` does; the received planes of each split dim are
        then concatenated onto the block — no pad + scatter pairs, and XLA
        folds the nested concats into a single copy.  Later dims' slabs
        span the already-extended extents, so 27-pt corner neighbours
        arrive exactly as in the scatter form.  (Zero planes concatenated
        onto the unsplit dims gave the same values, but XLA's CPU backend
        rounded the 7-point apply of that operand differently from the
        global one, by up to 7.6e-6 on N(0, 1) data.)
        """
        split = self.split_dims
        xp = jnp.pad(x, [(0, 0) if d in split else (1, 1) for d in range(3)])
        for d in split:
            axis = self.layout.dim_axes[d]
            n = self.layout.mesh.shape[axis]
            sl_lo = [slice(None)] * 3
            sl_hi = [slice(None)] * 3
            sl_lo[d] = slice(0, 1)
            sl_hi[d] = slice(xp.shape[d] - 1, xp.shape[d])
            lo = lax.ppermute(xp[tuple(sl_hi)], axis,
                              [(i, i + 1) for i in range(n - 1)])
            hi = lax.ppermute(xp[tuple(sl_lo)], axis,
                              [(i + 1, i) for i in range(n - 1)])
            xp = jnp.concatenate([lo, xp, hi], axis=d)
        return xp

    def matvec(self, x: jax.Array) -> jax.Array:
        if self.halo_mode == "overlap":
            return self._matvec_overlap(x)
        # fork-join: the barrier keeps XLA from taking the slices that lie
        # inside the block from ``x`` instead of the padded operand, so the
        # whole apply waits for every received plane
        return self._mv_padded(lax.optimization_barrier(self.pad_exchange(x)))

    def matvec_local(self, x: jax.Array) -> jax.Array:
        """Zero-halo apply on the local block ONLY — no ppermutes.

        The block-diagonal operator of the block-Jacobi preconditioner
        (two-stage multisplitting): decomposed faces are treated as
        physical boundary, so the apply is communication-free.
        """
        return self._mv_padded(jnp.pad(x, 1))

    def _overlap_split(self, x: jax.Array) -> tuple[int, ...]:
        """The split dims of the interior/shell split; none where nothing is
        decomposed or a block is one plane thick (no interior)."""
        split = self.split_dims
        if not split or min(x.shape[d] for d in split) < 2:
            return ()
        return split

    def _matvec_overlap(self, x: jax.Array) -> jax.Array:
        """Overlapped halo-exchange SpMV (the paper's task-based split).

        The ppermutes are issued first; the interior — every output cell at
        distance >= 1 from a decomposed face, i.e. almost the whole block —
        depends only on ``x``, so the latency-hiding scheduler can run it
        while the collectives are in flight.  Only the one-cell boundary
        shell consumes the received planes.  The ``optimization_barrier``
        pins the interior as its own schedulable task (the same idiom that
        keeps bicgstab_b1's reduction overlap windows from fusing away).
        Solver results are bit-for-bit identical to the concat/scatter
        modes (tests/test_halo_overlap.py).
        """
        split = self._overlap_split(x)
        xp = self._pad_exchange_concat(x)
        if not split:
            return self._mv_padded(xp)
        y_int = lax.optimization_barrier(
            interior_matvec(self._mv_padded, x, split))
        return shell_assemble(self._mv_padded, xp, y_int, split)

    def matvec_dot(self, x: jax.Array) -> tuple:
        """``(A x, x·A x)`` with the dot's one psum under ``repro.reduce``.

        The built-in box apply computes the local partial in its own pass
        (``Stencil.matvec_dot_padded``), split as under ``overlap``: the
        interior's partial rides the interior's barrier and each shell slab
        adds its own, in a fixed order.  The fork-join modes run the same
        split on their exchanged operand, so every halo mode gives the same
        bits and still waits for every received plane.  ``None`` for any
        other apply.
        """
        split = self._overlap_split(x)
        if self.halo_mode == "overlap":
            xp = self._pad_exchange_concat(x)
        else:
            # fork-join: the interior reads the block out of the exchanged
            # operand, so it too waits for every received plane
            xp = lax.optimization_barrier(self.pad_exchange(x))
            x = xp[1:-1, 1:-1, 1:-1]
        if not split:
            y, xy = self._pair_padded(xp)
        else:
            y, xy = lax.optimization_barrier(
                interior_matvec(self._pair_padded, x, split))
        for d, lo, hi in shell_slabs(x.shape, split):
            (y_lo, xy_lo), (y_hi, xy_hi) = (
                self._pair_padded(slab_operand(xp, box)) for box in (lo, hi))
            y = jnp.concatenate([y_lo, y, y_hi], axis=d)
            with jax.named_scope("repro.reduce"):
                xy = xy + xy_lo + xy_hi
        (xy,) = self.sum_partials(xy)
        return y, xy

    def _pair_padded(self, xp: jax.Array) -> tuple:
        return self.stencil.matvec_dot_padded(xp, local_dot)

    # --- global reductions (the paper's MPI_Allreduce) -----------------------
    def dot(self, a: jax.Array, b: jax.Array) -> jax.Array:
        # single psum over the tuple of axes == ONE all-reduce (one barrier),
        # exactly like one MPI_Allreduce over the world communicator.
        return lax.psum(local_dot(a, b), self.layout.reduce_axes)

    def dotn(self, *pairs) -> tuple:
        """Any number of dot products in ONE collective: stack the local
        partials, single psum, unstack.  The merged/pipelined Krylov
        variants ride their entire per-iteration scalar traffic (2, 3 or 9
        dots) through this — one all-reduce per iteration, verified on the
        compiled HLO by tests/test_hlo_analysis.py."""
        stacked = lax.psum(
            jnp.stack([local_dot(a, b) for a, b in pairs]),
            self.layout.reduce_axes)
        return tuple(stacked[i] for i in range(len(pairs)))

    def dot2(self, a, b, c, d):
        """Two dot products in ONE collective (the paper fuses scalar pairs
        into a single MPI_Allreduce)."""
        return self.dotn((a, b), (c, d))

    @in_scope("repro.reduce")
    def sum_partials(self, *vals) -> tuple:
        """Globally reduce already-computed local partial scalars in ONE
        collective — the fused Pallas kernels' dot partials (accumulated
        per block inside the kernel) ride this to become global dots."""
        stacked = lax.psum(jnp.stack(vals), self.layout.reduce_axes)
        return tuple(stacked[i] for i in range(len(vals)))

def make_layout(mesh: Mesh, dims_map: dict[str, str | None] | None = None) -> GridLayout:
    """Default layouts per mesh:

    * ('data','model')        -> x: model, y: data, z: unsplit  (single pod)
    * ('pod','data','model')  -> x: model, y: data, z: pod      (multi pod)
    * 1-D mesh ('cells',)     -> z: cells (the paper-faithful HPCCG layout)
    """
    names = mesh.axis_names
    if dims_map is not None:
        da = (dims_map.get("x"), dims_map.get("y"), dims_map.get("z"))
        return GridLayout(mesh=mesh, dim_axes=da)
    if names == ("cells",):
        return GridLayout(mesh=mesh, dim_axes=(None, None, "cells"))
    if names == ("data", "model"):
        return GridLayout(mesh=mesh, dim_axes=("model", "data", None))
    if names == ("pod", "data", "model"):
        return GridLayout(mesh=mesh, dim_axes=("model", "data", "pod"))
    raise ValueError(f"no default layout for mesh axes {names}")


def _local_ops(stencil, layout, b_loc, *, matvec_padded, halo_mode,
               precond, norm_ref, pallas_fused):
    """Build the DistributedOp (optionally Pallas-wrapped) + Ops context for
    one shard_map body — shared by solve_shardmap and solve_step_shardmap."""
    op = DistributedOp(stencil, layout, matvec_padded=matvec_padded,
                       halo_mode=halo_mode)
    if pallas_fused:
        from repro.kernels.pallas_op import PallasOp
        op = PallasOp(op)
    M = precond.bind(op) if precond is not None else None
    return Ops(op, b_loc, M=M, norm_ref=norm_ref)


def _check_method(method: str, precond, pallas_fused: bool,
                  matvec_padded=None):
    """Resolve + validate a method name for the distributed drivers.

    Raises a ``ValueError`` listing the known methods for an unregistered
    name (previously ``solve_step_shardmap`` fell through silently until
    trace time), and rejects precond/fused requests the definition does not
    support.
    """
    from repro.core.methods import METHODS
    mdef = get_method(method)          # ValueError w/ known-method list
    if precond is not None and not mdef.accepts_precond:
        raise ValueError(
            f"method {method!r} takes no preconditioner; use one of "
            f"{sorted(n for n, m in METHODS.items() if m.accepts_precond)}")
    if pallas_fused and not mdef.has_fused_body:
        raise ValueError(
            f"method {method!r} declares no fused kernels; fused methods: "
            f"{sorted(n for n, m in METHODS.items() if m.has_fused_body)}")
    if pallas_fused and matvec_padded is not None:
        # the fused body's SpMVs run the built-in Pallas stencil kernel —
        # a custom matvec_padded would apply only to the (unfused) initial
        # residual, i.e. a solve against two different operators
        raise ValueError(
            "pallas_fused=True is incompatible with a custom matvec_padded "
            "(the fused kernels implement the built-in stencil apply)")
    return mdef


def solve_shardmap(
    problem: HPCGProblem,
    method: str,
    mesh: Mesh,
    *,
    dims_map: dict[str, str | None] | None = None,
    tol: float = 1e-6,
    maxiter: int = 600,
    norm_ref: float | None = 1.0,   # paper: absolute ||r|| < eps (HPCCG criterion)
    matvec_padded: Callable | None = None,
    halo_mode: str = "auto",
    precond=None,
    pallas_fused: bool = False,
    telemetry: int = 0,
    guard_spec=None,
    refresh_every: int = 0,
):
    """Build the shard_map-wrapped distributed solver; returns (fn, in_specs).

    ``fn(b, x0) -> SolveResult`` with b/x0 GLOBAL arrays sharded per layout.
    The solve is the method's ``MethodDef`` run by the generic
    ``run_method`` driver over a ``DistributedOp`` — the identical
    definition the local path executes.  ``precond`` is a
    ``repro.precond.Preconditioner`` (or None); it is bound to the operator
    *inside* shard_map, so its applies see the local block and the mesh's
    halo machinery.  ``pallas_fused=True`` wraps the operator in a
    ``PallasOp`` and runs the method's fused-kernel body (methods that
    declare one, e.g. ``cg_merged``) — the fused kernels execute inside
    the shard_map body, halos and psums included.  ``telemetry=N``
    (repro.obs) threads the driver's bounded scalar-history buffer through
    the loop carry; the recorded scalars are post-psum (replicated), so the
    buffer rides an unsharded ``P()`` out_spec.  ``telemetry=0`` keeps the
    out-spec tree (and the lowered HLO) bit-for-bit the pre-telemetry one.

    Resilience (repro.resilience): ``guard_spec``/``refresh_every`` are
    forwarded to the driver.  Guards compare post-psum (replicated)
    scalars, so every shard exits the while-loop on the same iteration
    with no extra collectives; the residual-replacement ``lax.cond`` body
    re-runs the method's own halo exchange + stacked psum, so both
    branches stay replication-consistent under shard_map.  The typed
    ``status`` scalar is replicated and rides a ``P()`` out_spec.
    """
    mdef = _check_method(method, precond, pallas_fused, matvec_padded)
    layout = make_layout(mesh, dims_map)
    stencil = problem.stencil

    def local_solve(b_loc: jax.Array, x0_loc: jax.Array) -> SolveResult:
        ops = _local_ops(stencil, layout, b_loc, matvec_padded=matvec_padded,
                         halo_mode=halo_mode, precond=precond,
                         norm_ref=norm_ref, pallas_fused=pallas_fused)
        return run_method(mdef, ops, x0_loc, tol=tol, maxiter=maxiter,
                          fused=pallas_fused, telemetry=telemetry,
                          guard_spec=guard_spec, refresh_every=refresh_every)

    spec = layout.spec()
    fn = jax.shard_map(
        local_solve,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=SolveResult(x=spec, iters=P(), res_norm=P(), history=P(),
                              telemetry=P() if telemetry else None,
                              status=P()),
        # Pallas's interpreter (the kernels off a TPU) does not carry
        # varying-axes types through its grid loop
        check_vma=not pallas_fused,
    )
    return fn, layout


def step_state_layout(method: str) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """(vector slot names, scalar slot names) of a method's step state —
    derived mechanically from its ``MethodDef`` (the hand-written
    ``STEP_STATE`` table this replaces is gone; tests assert the derived
    layouts match the documented ones)."""
    mdef = get_method(method)
    return mdef.vectors, mdef.scalars


def init_step_state(method: str, A, b, x0, M=None) -> tuple:
    """The full argument tuple ``(b, *vectors, *scalars)`` feeding one
    ``solve_step_shardmap`` iteration, matching the solver's loop carry at
    iteration 0 (so one step == one ``lax.while_loop`` body —
    tests/test_step_parity.py).  ``A`` is any LocalOp-protocol operator;
    ``M`` the bound preconditioner apply for the methods that take one.
    Derived mechanically from the method's ``MethodDef.init``.
    """
    mdef = get_method(method)
    ops = Ops(A, b, M=M, norm_ref=1.0)
    return (b, *mdef.init(ops, x0))


def solve_step_shardmap(
    problem: HPCGProblem,
    method: str,
    mesh: Mesh,
    *,
    dims_map: dict[str, str | None] | None = None,
    matvec_padded: Callable | None = None,
    halo_mode: str = "auto",
    precond=None,
    pallas_fused: bool = False,
):
    """One *iteration* of the solver as a standalone shard_mapped function.

    Used by the dry-run/roofline: lowering a single iteration makes
    ``cost_analysis`` exact (no while-loop trip-count ambiguity) and exposes
    the per-iteration collective schedule for the overlap analysis.  The
    body IS the method's ``MethodDef.step`` (no per-method dispatch here);
    the state signature is ``(b, *vectors, *scalars)`` per
    :func:`step_state_layout` and :func:`init_step_state` builds a matching
    initial tuple.  Unknown method names raise a ``ValueError`` listing the
    registry (they previously fell through to a trace-time error).
    ``pallas_fused=True`` lowers the fused-kernel body instead.
    """
    mdef = _check_method(method, precond, pallas_fused, matvec_padded)
    layout = make_layout(mesh, dims_map)
    stencil = problem.stencil
    step = mdef.fused_step if pallas_fused else mdef.step

    def local_step(b_loc, *state):
        ops = _local_ops(stencil, layout, b_loc, matvec_padded=matvec_padded,
                         halo_mode=halo_mode, precond=precond,
                         norm_ref=1.0, pallas_fused=pallas_fused)
        return tuple(step(ops, state))

    spec = layout.spec()
    nvec, nscal = len(mdef.vectors), len(mdef.scalars)
    fn = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(spec,) * (1 + nvec) + (P(),) * nscal,
        out_specs=(spec,) * nvec + (P(),) * nscal,
        check_vma=not pallas_fused,   # as in solve_shardmap
    )
    return fn, layout

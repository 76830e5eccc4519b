"""The solver facade: one entry point for every execution world.

``SolverSession`` binds (problem, method, options) to a resolved backend and
compiles the solve once; ``solve()`` / ``solve_batched()`` are the one-shot
conveniences.  The paper's "write the algorithm once, swap the parallelisation
underneath" now holds at the user surface too:

    from repro.api import solve, SolverOptions
    res = solve(method="cg_nb", grid=(64, 64, 64), stencil="27pt",
                options=SolverOptions(tol=1e-6, maxiter=600))

runs ``LocalOp`` on one device, the paper-faithful 1-D shard_map decomposition
on many, and the Pallas stencil kernel when ``options.pallas`` is set — with
identical ``SolveResult`` semantics everywhere.

``solve_batched`` is the serving path: many right-hand sides solved in ONE
compiled call.  Locally the solver is vmapped; on a mesh the vmap happens
*inside* shard_map, so the batch rides the same halo exchanges and each
reduction stays one ``psum`` per iteration for the whole batch.  JAX's
batching rule for ``while_loop`` masks finished lanes, so each RHS converges
exactly as it would alone (same iteration count, same iterates).
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.backend import (Backend, resolve_backend, resolve_halo_mode,
                               resolve_matvec, resolve_precond)
from repro.api.options import SolverOptions
from repro.api.registry import REGISTRY, fallback_chain, get_solver
from repro.api.timing import timed_result
from repro.core.distributed import DistributedOp, solve_shardmap, solve_step_shardmap
from repro.core.methods import (STATUS_BREAKDOWN, STATUS_DIVERGED,
                                STATUS_STAGNATED, MethodDef, SolveBreakdown,
                                status_name)
from repro.core.problems import HPCGProblem, make_problem
from repro.core.solvers import SOLVERS, LocalOp, SolveResult
from repro.obs import trace as obs
from repro.obs.scopes import op_scopes

#: guarded exit statuses the recovery policies act on
_RECOVERABLE = (STATUS_BREAKDOWN, STATUS_DIVERGED, STATUS_STAGNATED)


class SolverSession:
    """A problem + method + options bound to a resolved backend.

    Reuse a session to amortise compilation across repeated solves (the
    serving loop); use the module-level :func:`solve` for one-offs.
    """

    def __init__(self, problem: HPCGProblem | None = None, *,
                 method: str = "cg_nb",
                 grid: tuple[int, int, int] | None = None,
                 stencil: str = "27pt",
                 options: SolverOptions | None = None,
                 mesh: Mesh | None = None,
                 backend: Backend | None = None):
        self.options = options or SolverOptions()
        if problem is None:
            if grid is None:
                raise ValueError("need either a problem or a grid")
            if self.options.f64 and not jax.config.jax_enable_x64:
                raise ValueError(
                    "SolverOptions.f64=True but jax x64 is disabled.  The "
                    "facade no longer flips the process-global "
                    "jax_enable_x64 flag implicitly: call "
                    "repro.core.problems.enable_f64() at process start "
                    "(x64 is not a per-computation switch in JAX) or pass "
                    "SolverOptions(f64=False).")
            dtype = jnp.float64 if self.options.f64 else jnp.float32
            problem = make_problem(tuple(grid), stencil, dtype=dtype)
        else:
            want = jnp.float64 if self.options.f64 else jnp.float32
            have = jnp.dtype(problem.dtype)
            if have != jnp.dtype(want):
                raise ValueError(
                    f"SolverOptions.f64={self.options.f64} conflicts with the "
                    f"pre-built problem's dtype {have.name}; pass "
                    f"f64={have == jnp.dtype(jnp.float64)} (the problem's "
                    f"dtype is authoritative) or rebuild the problem.")
        self.problem = problem
        if self.options.pallas is None:
            # pallas="auto": the kernels/autotune cache (or its documented
            # default table) decides the Pallas-vs-XLA routing for this
            # (stencil, grid, dtype, device_kind); downstream code only
            # ever sees a concrete bool.
            from repro.kernels import autotune
            dec = autotune.resolve(problem.stencil.name, problem.shape,
                                   problem.dtype)
            self.options = self.options.replace(pallas=dec.use_pallas)
        if self.options.pallas or (self.options.precond_params or {}).get(
                "use_pallas"):
            from repro.kernels import ops
            ops.check_dtype(problem.dtype)
        # solve-lifecycle spans (repro.obs): resolve -> precond.setup ->
        # solve (inputs, with compile in _executable on a miss; execute)
        with obs.span("resolve", method=method, layout=self.options.layout,
                      grid=list(problem.shape)):
            self.mdef: MethodDef = get_solver(method)
            self.backend: Backend = backend or resolve_backend(self.options,
                                                               mesh=mesh)
            self._matvec = resolve_matvec(problem.stencil, self.options)
            self.halo_mode = resolve_halo_mode(self.options)
        with obs.span("precond.setup", precond=self.options.precond):
            self.precond = resolve_precond(self.options)
        if self.precond is not None and not self.mdef.accepts_precond:
            takers = sorted(n for n, s in REGISTRY.items()
                            if s.accepts_precond)
            raise ValueError(
                f"method {self.method!r} takes no preconditioner; use one "
                f"of {takers} with precond={self.options.precond!r}, or "
                f"precond='none'")
        if (self.precond is not None and self.mdef.spd_required
                and not self.precond.spd_preserving):
            raise ValueError(
                f"method {self.method!r} requires an SPD-preserving "
                f"preconditioner, but {self.precond.describe()} declares "
                f"spd_preserving=False; use pbicgstab or an SPD-preserving "
                f"M (CG's short recurrence silently breaks down otherwise)")
        if self.options.residual_replacement and not self.mdef.has_refresh:
            raise ValueError(
                f"residual_replacement={self.options.residual_replacement} "
                f"but method {self.method!r} declares no refresh hook; "
                f"residual replacement targets the merged/pipelined variants "
                f"(MethodDef.refresh) — the classical recurrences already "
                f"compute the true residual")
        # kept for the fallback ladder (sessions rebuilt on the same mesh)
        self._mesh_arg = mesh if mesh is not None else getattr(
            self.backend, "mesh", None)
        self._fallbacks: list[tuple[str, "SolverSession"]] | None = None
        # AOT-compiled executables keyed by input shape: ``grid`` for the
        # single-RHS solve, ``(batch, *grid)`` for the batched one.  Each
        # entry is a ``jax.stages.Compiled`` (the ``.lower().compile()``
        # product of the same jitted builders the lazy path used), so the
        # session can report honest per-shape compile seconds and hit/miss
        # counts — the observability ``repro.serve``'s executable cache and
        # its CI gate are built on.
        self._executables: dict[tuple, Any] = {}
        self._compile_stats: dict[tuple, dict] = {}
        self._timed_fn = None         # undonated variants for timed_*
        self._timed_batched_fn = None  # (repeat calls reuse input buffers)

    # -- introspection --------------------------------------------------------
    @property
    def method(self) -> str:
        return self.mdef.name

    @property
    def layout(self):
        return self.backend.layout

    def describe(self) -> str:
        pre = (f" precond={self.precond.describe()}"
               if self.precond is not None else "")
        return (f"{self.method}/{self.problem.stencil.name} "
                f"grid={self.problem.shape} on {self.backend.describe()}"
                f"{' [pallas]' if self.options.pallas else ''}{pre}")

    def _solver_kwargs(self, A) -> dict:
        """tol/maxiter/norm_ref plus the bound preconditioner apply (and
        the telemetry row bound when convergence telemetry is on — only
        passed when enabled)."""
        kw = self.options.solver_kwargs()
        if self.mdef.accepts_precond:
            kw["M"] = None if self.precond is None else self.precond.bind(A)
        rows = self.options.telemetry_rows()
        if rows:
            kw["telemetry"] = rows
        gs = self.options.guard_spec()
        if gs is not None:
            kw["guard_spec"] = gs
        if self.options.residual_replacement:
            kw["refresh_every"] = self.options.residual_replacement
        return kw

    def _use_fused_body(self) -> bool:
        """Route ``pallas=True`` solves of any method whose ``MethodDef``
        declares a fused kernel body (the registry's ``has_fused_body``
        capability — not a hard-coded method name) to the fully fused
        iteration: e.g. merged CG's SpMV *and* its two dot partials in one
        VMEM pass, the four vector updates in another — instead of merely
        swapping the SpMV under the jnp solver.  Works on the local AND the
        shard_map backend (``PallasOp`` supplies halos/psums there).
        Single-RHS solves only: the batched path always runs the jnp body
        (with the Pallas SpMV under ``pallas=True``) — vmapping the fused
        kernels is not supported.  Preconditioned methods stay on the
        fused path too (PR 10): the bound preconditioner apply composes
        inside the fused body (its own Pallas kernels when
        ``use_pallas``), so ``pcg_merged + chebyshev`` runs end-to-end on
        the 2-HBM-pass path."""
        return (self.options.pallas and self.mdef.has_fused_body
                and (self.precond is None or self.mdef.accepts_precond)
                and self.options.matvec_padded is None
                and self.options.dot is None)

    # -- single-RHS path ------------------------------------------------------
    def _build_fn(self, *, donate: bool | None = None):
        opts = self.options
        donate = opts.donate if donate is None else donate
        # donating x0 lets XLA alias the x/r/p iterate chain onto the
        # caller's buffer (input_output_alias in the lowered HLO); b stays
        # un-donated — the stationary methods re-read it every iteration
        # and callers routinely keep it.
        jit_kw = dict(donate_argnums=(1,)) if donate else {}
        if self._use_fused_body():
            if self.backend.kind == "local":
                from repro.core.methods import Ops, run_method
                from repro.kernels.pallas_op import PallasOp
                A = PallasOp(LocalOp(self.problem.stencil))
                mdef = self.mdef
                M = (None if self.precond is None
                     else self.precond.bind(A))

                def run_fused(b, x0):
                    ops = Ops(A, b, M=M, norm_ref=opts.norm_ref)
                    return run_method(mdef, ops, x0, tol=opts.tol,
                                      maxiter=opts.maxiter, fused=True,
                                      telemetry=opts.telemetry_rows(),
                                      guard_spec=opts.guard_spec(),
                                      refresh_every=opts.residual_replacement)

                return jax.jit(run_fused, **jit_kw)
            # fused kernels inside the shard_map body (PallasOp wraps the
            # DistributedOp for halos + the stacked partial-dot psum)
            fn, _ = solve_shardmap(
                self.problem, self.method, self.backend.mesh,
                dims_map=opts.dims_map, tol=opts.tol, maxiter=opts.maxiter,
                norm_ref=opts.norm_ref, halo_mode=self.halo_mode,
                pallas_fused=True, precond=self.precond,
                telemetry=opts.telemetry_rows(),
                guard_spec=opts.guard_spec(),
                refresh_every=opts.residual_replacement)
            return jax.jit(fn, **jit_kw)
        if self.backend.kind == "local":
            A = LocalOp(self.problem.stencil, matvec_padded=self._matvec)

            def run(b, x0):
                return SOLVERS[self.method](A, b, x0, dot=opts.dot,
                                            **self._solver_kwargs(A))

            return jax.jit(run, **jit_kw)
        fn, _ = solve_shardmap(
            self.problem, self.method, self.backend.mesh,
            dims_map=opts.dims_map, tol=opts.tol, maxiter=opts.maxiter,
            norm_ref=opts.norm_ref, matvec_padded=self._matvec,
            halo_mode=self.halo_mode, precond=self.precond,
            telemetry=opts.telemetry_rows(),
            guard_spec=opts.guard_spec(),
            refresh_every=opts.residual_replacement)
        return jax.jit(fn, **jit_kw)

    def _place(self, x: jax.Array, *, batched: bool = False) -> jax.Array:
        sh = self.backend.sharding()
        if sh is None:
            return x
        if batched:
            sh = NamedSharding(self.backend.mesh,
                               P(None, *self.layout.dim_axes))
        return jax.device_put(x, sh)

    # -- compiled-executable cache (observability for the serving layer) ------
    def _executable(self, shape: tuple, builder, example_args: tuple):
        """Return the AOT-compiled executable for ``shape``, compiling (and
        recording honest wall-clock compile seconds) on first use."""
        ent = self._executables.get(shape)
        st = self._compile_stats.setdefault(
            (shape, self.method, self.options.precond),
            {"hits": 0, "misses": 0, "compile_s": 0.0})
        if ent is None:
            with obs.span("compile", method=self.method, shape=list(shape),
                          backend=self.backend.kind):
                t0 = time.perf_counter()
                ent = builder().lower(*example_args).compile()
                st["misses"] += 1
                st["compile_s"] += time.perf_counter() - t0
                self._executables[shape] = ent
        else:
            st["hits"] += 1
        return ent

    def cache_stats(self) -> dict[tuple, dict]:
        """Compile-cache observability: ``{(shape, method, precond):
        {"hits", "misses", "compile_s"}}``.  ``shape`` is the problem grid
        for single-RHS solves and ``(batch, *grid)`` for batched ones; a
        miss is one real XLA compile (``jit(...).lower().compile()``) and
        ``compile_s`` its measured wall-clock cost.  ``repro.serve``'s
        executable cache asserts "exactly one compile per bucket" against
        these counters."""
        return {k: dict(v) for k, v in self._compile_stats.items()}

    def op_scopes(self) -> dict[tuple[str, str], str]:
        """``{(HLO module, op name): repro.* scope}`` over every
        executable the session has compiled, read from their metadata
        (``repro.obs.scopes``; docs/API.md §Observability).  Computed on
        each call, never at compile time."""
        out: dict[tuple[str, str], str] = {}
        for ex in (*self._executables.values(), self._timed_fn):
            if ex is not None:
                out.update(op_scopes(ex))
        return out

    def _abstract(self, shape: tuple, *, batched: bool = False):
        dt = jnp.dtype(self.problem.dtype)
        sh = self.backend.sharding()
        if sh is not None and batched:
            sh = NamedSharding(self.backend.mesh,
                               P(None, *self.layout.dim_axes))
        if sh is None:
            return jax.ShapeDtypeStruct(shape, dt)
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    def compile_batched(self, batch: int) -> float:
        """Compile the ``batch``-RHS executable ahead of time (no solve
        executes) and return the compile seconds; a later
        :meth:`solve_batched` at this batch size is a cache hit.  This is
        the serve layer's compile-then-admit hook: a cold bucket compiles
        off the serving path and only then starts taking batches."""
        shape = (batch, *self.problem.shape)
        ab = self._abstract(shape, batched=True)
        t0 = time.perf_counter()
        self._executable(shape, self._build_batched_fn, (ab, ab))
        return time.perf_counter() - t0

    def _solve_once(self, b: jax.Array | None = None,
                    x0: jax.Array | None = None) -> SolveResult:
        """One compiled solve, no recovery policy (the :meth:`solve` body
        pre-resilience; restart/fallback attempts re-enter here)."""
        with obs.span("solve", method=self.method,
                      grid=list(self.problem.shape),
                      backend=self.backend.kind):
            with obs.span("inputs"):
                b = self.problem.b() if b is None else b
                if x0 is None:
                    x0 = self.problem.x0(self.backend.sharding())
                fn = self._executable(
                    tuple(self.problem.shape), self._build_fn,
                    (self._abstract(tuple(self.problem.shape)),) * 2)
                b, x0 = self._place(b), self._place(x0)
            with obs.span("execute") as sp:
                res = fn(b, x0)
                if sp is not None:
                    # only when tracing: block so the span times the solve,
                    # not the async dispatch (result semantics unchanged)
                    res = jax.block_until_ready(res)
        return res

    def solve(self, b: jax.Array | None = None,
              x0: jax.Array | None = None) -> SolveResult:
        """Solve one system, applying ``options.on_breakdown`` when the
        breakdown guards are armed and the solve exits with an abnormal
        typed status (breakdown / diverged / stagnated):

        * ``"raise"``    — raise :class:`SolveBreakdown` (result attached);
        * ``"none"``     — return the result, status untouched;
        * ``"restart"``  — re-solve from the last finite iterate (zeros if
          the iterate is poisoned), up to ``max_restarts`` attempts;
        * ``"fallback"`` — walk the robustness ladder: the same method on
          the plain XLA path first, then each ``variant_of`` ancestor down
          to the classical method.

        With guards disarmed (the default) this is exactly the compiled
        solve — no status inspection, no host sync.  Each recovery attempt
        is traced as a ``resilience.attempt`` span (repro.obs)."""
        res = self._solve_once(b, x0)
        opts = self.options
        if opts.guard_spec() is None or opts.on_breakdown == "none":
            return res
        if int(res.status) not in _RECOVERABLE:
            return res
        return self._recover(res, b)

    def _recover(self, res: SolveResult, b: jax.Array | None) -> SolveResult:
        """Apply the armed ``on_breakdown`` policy to an abnormal exit."""
        opts = self.options
        if opts.on_breakdown == "raise":
            raise SolveBreakdown(self.method, res)
        b = self.problem.b() if b is None else b
        if opts.on_breakdown == "restart":
            for attempt in range(1, opts.max_restarts + 1):
                x_start = res.x
                if not bool(jnp.all(jnp.isfinite(x_start))):
                    x_start = jnp.zeros_like(b)
                with obs.span("resilience.attempt", policy="restart",
                              method=self.method, attempt=attempt,
                              from_status=status_name(res.status)):
                    res = self._solve_once(b, x_start)
                if int(res.status) not in _RECOVERABLE:
                    break
            return res
        for attempt, (name, sess) in enumerate(self._fallback_ladder(), 1):
            if attempt > max(1, opts.max_restarts):
                break
            with obs.span("resilience.attempt", policy="fallback",
                          method=name, attempt=attempt,
                          from_status=status_name(res.status)):
                res = sess._solve_once(b, None)
            if int(res.status) not in _RECOVERABLE:
                break
        return res

    def _fallback_ladder(self) -> list[tuple[str, "SolverSession"]]:
        """Sessions the ``"fallback"`` policy walks, built lazily and cached
        for the session's lifetime: the same method with every kernel
        override retreated to the reference XLA operator (Pallas / custom
        ``matvec_padded`` / custom ``dot`` dropped) when one was active,
        then each ``variant_of`` ancestor down to the classical method —
        the preconditioner is dropped for rungs without an ``M=`` hook and
        residual replacement for rungs without a refresh hook.  Ladder
        sessions run with guards armed but ``on_breakdown="none"``: their
        typed status gates the walk without recursing into recovery."""
        if self._fallbacks is not None:
            return self._fallbacks
        opts = self.options
        base = opts.replace(on_breakdown="none", guards=True, pallas=False,
                            matvec_padded=None, dot=None)
        plan: list[tuple[str, SolverOptions]] = []
        if (opts.pallas or opts.matvec_padded is not None
                or opts.dot is not None):
            plan.append((self.method, base))
        for name in fallback_chain(self.method)[1:]:
            mdef = get_solver(name)
            o = base
            if not mdef.accepts_precond and o.precond != "none":
                o = o.replace(precond="none", precond_params=None)
            if o.residual_replacement and not mdef.has_refresh:
                o = o.replace(residual_replacement=0)
            plan.append((name, o))
        self._fallbacks = [
            (name, SolverSession(self.problem, method=name, options=o,
                                 mesh=self._mesh_arg))
            for name, o in plan]
        return self._fallbacks

    def timed_solve(self, b: jax.Array | None = None,
                    x0: jax.Array | None = None, *,
                    repeats: int = 10,
                    warmup: int = 1) -> tuple[SolveResult, dict[str, float]]:
        """Solve with honest wall-clock stats: warm-up (compile) happens
        outside the timed region and every call blocks until ready.  Uses
        an undonated compile (repeat calls reuse the same input buffers)."""
        with obs.span("solve", method=self.method,
                      grid=list(self.problem.shape),
                      backend=self.backend.kind, timed=True,
                      repeats=repeats):
            b = self._place(self.problem.b() if b is None else b)
            x0 = self._place(self.problem.x0(self.backend.sharding())
                             if x0 is None else x0)
            if self._timed_fn is None:
                # the jit is lazy, so AOT-lower here to give the compile its
                # own honest span (warm-up inside timed_result would
                # otherwise absorb it invisibly)
                with obs.span("compile", method=self.method,
                              shape=list(self.problem.shape),
                              backend=self.backend.kind):
                    self._timed_fn = (self._build_fn(donate=False)
                                      .lower(b, x0).compile())
            with obs.span("execute"):
                return timed_result(self._timed_fn, b, x0, repeats=repeats,
                                    warmup=warmup)

    # -- batched multi-RHS path (the serving workload) ------------------------
    def _build_batched_fn(self, *, donate: bool | None = None):
        opts = self.options
        donate = opts.donate if donate is None else donate
        jit_kw = dict(donate_argnums=(1,)) if donate else {}
        if self.backend.kind == "local":
            A = LocalOp(self.problem.stencil, matvec_padded=self._matvec)

            def run(b, x0):
                return SOLVERS[self.method](A, b, x0, dot=opts.dot,
                                            **self._solver_kwargs(A))

            return jax.jit(jax.vmap(run), **jit_kw)

        layout = self.layout
        stencil = self.problem.stencil

        def local_solve(b_loc, x0_loc):
            op = DistributedOp(stencil, layout, matvec_padded=self._matvec,
                               halo_mode=self.halo_mode)
            return SOLVERS[self.method](op, b_loc, x0_loc, dot=op.dot,
                                        **self._solver_kwargs(op))

        bspec = P(None, *layout.dim_axes)
        fn = jax.shard_map(
            jax.vmap(local_solve),
            mesh=self.backend.mesh,
            in_specs=(bspec, bspec),
            out_specs=SolveResult(
                x=bspec, iters=P(), res_norm=P(), history=P(),
                telemetry=P() if opts.telemetry_rows() else None,
                status=P()),
        )
        return jax.jit(fn, **jit_kw)

    def _prep_batched(self, bs, x0s):
        """Validate + place a batch; returns (bs, x0s)."""
        if bs.ndim != 4:
            raise ValueError(f"bs must be (batch, nx, ny, nz), got {bs.shape}")
        if bs.shape[1:] != self.problem.shape:
            raise ValueError(
                f"RHS grid {bs.shape[1:]} != problem grid {self.problem.shape}")
        if x0s is None:
            x0s = jnp.zeros_like(bs)
        return self._place(bs, batched=True), self._place(x0s, batched=True)

    def solve_batched(self, bs: jax.Array,
                      x0s: jax.Array | None = None) -> SolveResult:
        """Solve ``bs.shape[0]`` right-hand sides in one compiled call.

        ``bs``/``x0s``: (batch, nx, ny, nz); ``x0s`` defaults to zeros.
        Returns a ``SolveResult`` whose leaves carry a leading batch axis.
        ``on_breakdown`` recovery never applies here: one poisoned lane
        must not raise or re-solve the whole batch — callers (the serve
        layer's poison quarantine) read the per-lane ``status`` instead.
        """
        with obs.span("solve", method=self.method,
                      grid=list(self.problem.shape),
                      backend=self.backend.kind, batch=int(bs.shape[0])):
            bs, x0s = self._prep_batched(bs, x0s)
            shape = tuple(bs.shape)
            fn = self._executable(shape, self._build_batched_fn,
                                  (self._abstract(shape, batched=True),) * 2)
            with obs.span("execute") as sp:
                res = fn(bs, x0s)
                if sp is not None:
                    res = jax.block_until_ready(res)
        return res

    def timed_solve_batched(self, bs: jax.Array,
                            x0s: jax.Array | None = None, *,
                            repeats: int = 10, warmup: int = 1
                            ) -> tuple[SolveResult, dict[str, float]]:
        """:meth:`solve_batched` with honest wall-clock stats (undonated
        compile — repeat calls reuse the same input buffers)."""
        bs, x0s = self._prep_batched(bs, x0s)
        if self._timed_batched_fn is None:
            self._timed_batched_fn = self._build_batched_fn(donate=False)
        return timed_result(self._timed_batched_fn, bs, x0s, repeats=repeats,
                            warmup=warmup)

    # -- analysis path (dry-run / roofline / barrier traces) ------------------
    def step_fn(self):
        """One solver *iteration* as a shard_mapped fn (exact cost analysis;
        see ``core.distributed.solve_step_shardmap``).  Mesh backends only."""
        if self.backend.kind != "shard_map":
            raise ValueError("step_fn needs a mesh backend")
        return solve_step_shardmap(
            self.problem, self.method, self.backend.mesh,
            dims_map=self.options.dims_map, matvec_padded=self._matvec,
            halo_mode=self.halo_mode, precond=self.precond)


# -- one-shot facades ---------------------------------------------------------

def _session(problem, method, grid, stencil, options, mesh,
             overrides: dict[str, Any]) -> SolverSession:
    options = options or SolverOptions()
    if overrides:
        options = options.replace(**overrides)
    return SolverSession(problem, method=method, grid=grid, stencil=stencil,
                         options=options, mesh=mesh)


def solve(problem: HPCGProblem | None = None, *, method: str = "cg_nb",
          grid: tuple[int, int, int] | None = None, stencil: str = "27pt",
          options: SolverOptions | None = None, mesh: Mesh | None = None,
          b: jax.Array | None = None, x0: jax.Array | None = None,
          **overrides) -> SolveResult:
    """Solve one system.  ``**overrides`` are ``SolverOptions`` fields
    (``tol=``, ``maxiter=``, ``pallas=``, ...) applied on top of ``options``."""
    sess = _session(problem, method, grid, stencil, options, mesh, overrides)
    return sess.solve(b=b, x0=x0)


def solve_batched(bs: jax.Array, problem: HPCGProblem | None = None, *,
                  method: str = "cg_nb",
                  grid: tuple[int, int, int] | None = None,
                  stencil: str = "27pt",
                  options: SolverOptions | None = None,
                  mesh: Mesh | None = None,
                  x0s: jax.Array | None = None,
                  **overrides) -> SolveResult:
    """Solve a batch of right-hand sides in one compiled call."""
    if bs.ndim != 4:
        raise ValueError(f"bs must be (batch, nx, ny, nz), got {bs.shape}")
    if grid is None and problem is None:
        grid = tuple(bs.shape[1:])
    sess = _session(problem, method, grid, stencil, options, mesh, overrides)
    return sess.solve_batched(bs, x0s=x0s)

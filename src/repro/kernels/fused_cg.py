"""Merged-reduction CG driven entirely by the fused Pallas kernels.

The fused iteration is no longer a hand-written loop: it is the
``cg_merged`` ``MethodDef``'s *fused body* (``repro.core.methods``),
executed by the same generic ``run_method`` driver as every other
backend, over a :class:`repro.kernels.pallas_op.PallasOp`:

    x, r, p, s = A.cg_body(α, β, x, r, p, s, w)        # 1 HBM pass
    w, δ, γ    = A.spmv_dots(r)                        # 1 HBM pass

Two passes per iteration versus the classic CG's five-to-six separate
kernel sweeps (SpMV, p·Ap, x-update, r-update, r·r, p-update) — the
kernel-switch fork-join barriers the paper's §3.3 task merging removes,
eliminated here as HBM round trips.  ``benchmarks/bench_kernels.py``
measures exactly this pairing; ``repro.api`` routes ``pallas=True`` solves
of any fused-capable method here (and to the shard_map equivalent on a
mesh — see ``core.distributed.solve_shardmap(pallas_fused=True)``).

Numerics: identical recurrence to ``cg_merged``; the fused dot partials
accumulate per x-plane instead of in jnp's reduction order, so iterates
agree to machine precision but not bit-for-bit
(tests/test_reduction_hiding.py pins the tolerance).
"""

from __future__ import annotations

import jax

from repro.core.methods import Ops, SolveResult, get_method, run_method
from repro.core.operators import Stencil
from repro.core.solvers import LocalOp
from repro.kernels.pallas_op import PallasOp


def cg_merged_fused(stencil: Stencil, b: jax.Array, x0: jax.Array, *,
                    tol: float = 1e-6, maxiter: int = 500,
                    norm_ref: float | None = None,
                    bz: int = 8) -> SolveResult:
    """Single-device merged CG, two fused HBM passes per iteration.

    Same signature semantics as the ``core.solvers`` methods (``norm_ref``
    ``None`` = relative to ``‖b‖``); jit-safe.
    """
    A = PallasOp(LocalOp(stencil), bz=bz)
    ops = Ops(A, b, norm_ref=norm_ref)
    return run_method(get_method("cg_merged"), ops, x0, tol=tol,
                      maxiter=maxiter, fused=True)

# Pallas TPU kernels for the compute hot-spots (pl.pallas_call + explicit
# BlockSpec VMEM tiling), each with a jit'd wrapper in ops.py and a pure-jnp
# oracle in ref.py (validated via interpret=True on CPU):
#
#   stencil_spmv     — 7/27-pt stencil SpMV, overlapping-window x-slabs,
#                      optional fused (A·x)·x partial (the paper's SpMV)
#   fused_axpby      — the paper's ad hoc z := a·x + b·y + c·z (+ fused dot)
#   cg_fused_update  — Alg.1 Tk1&2 in one VMEM pass (Ap, p updates + dot)
#   rb_gs            — red-black Gauss-Seidel half sweep (§3.4)
#   precond          — fused preconditioner steps: Chebyshev matvec+axpby
#                      chain and the block-Jacobi damped sweep, one VMEM pass
#   flash_attention  — causal online-softmax attention, (bq×bkv) VMEM tiles
#                      (the LM stack's chunked-attention endpoint)
from repro.kernels import ops, ref  # noqa: F401

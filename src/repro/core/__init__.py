# The paper's primary contribution: classical iterative methods (Jacobi,
# symmetric Gauss-Seidel, CG, BiCGStab) plus the communication-reducing
# variants (CG-NB, BiCGStab-B1, relaxed GS), written once and parallelised
# underneath via shard_map (DESIGN.md sections 2-3).
from repro.core.operators import (
    STENCIL_7PT,
    STENCIL_27PT,
    STENCILS,
    ELLOperator,
    Stencil,
    build_ell_from_stencil,
    touched_elements_per_iter,
)
from repro.core.methods import (
    METHODS,
    MethodDef,
    Ops,
    get_method,
    method_names,
    register_method,
    run_method,
)
from repro.core.problems import HPCGProblem, default_dtype, enable_f64, make_problem
from repro.core.solvers import (
    SOLVERS,
    LocalOp,
    SolveResult,
    bicgstab,
    bicgstab_b1,
    cg,
    cg_nb,
    jacobi,
    sym_gauss_seidel_rb,
    sym_gauss_seidel_relaxed,
)
from repro.core.distributed import (
    DistributedOp,
    GridLayout,
    make_layout,
    solve_shardmap,
    solve_step_shardmap,
)

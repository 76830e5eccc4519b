"""Static checks over the Pallas kernel layer (no kernel executes).

Three families of checks, mirroring how TPU kernels actually fail:

* **VMEM footprint**: each kernel streams blocks through ~16 MiB of VMEM;
  a block-shape change that fits interpret-mode CPU tests can still OOM on
  hardware.  We estimate the per-grid-step footprint from the block shapes
  at the *production* operating point (f32, 128×128 planes, the default
  slab depth; double-buffered) and fail when it exceeds the budget.  This is
  a model: the TPU compiler's own verdict at real sizes is
  ``tests/test_tpu_compile.py``.

* **Block divisibility**: the slab depth must tile the production grid
  extents and the test grids — ``slab_depth`` silently shrinks a
  non-dividing block (a perf cliff, not an error), so the lint makes the
  drift loud.

* **Completeness**: every module under ``repro.kernels`` containing a
  ``pallas_call`` must be covered by a table row; every row's wrapper must
  exist in ``kernels.ops``, its oracle in ``kernels.ref``, and a test row
  referencing ``ops.<name>`` in ``tests/test_kernels.py`` — the invariant
  ROADMAP.md states ("every kernel gets a ref.py oracle and a bench row").

The table is declarative so tests can inject a deliberately bad row
(oversized block) and assert this pass — and only this pass — flags it.
"""

from __future__ import annotations

import dataclasses
import inspect
import pathlib

from repro.analysis.violation import Violation

#: per-core VMEM, the budget the estimates are checked against
VMEM_BUDGET_BYTES = 16 * 2 ** 20
_DOUBLE_BUFFER = 2          # pallas pipelines block N+1's copy-in behind N
_ITEMSIZE = 4               # f32: what the kernels run on real TPUs (x64 is
                            # a CPU/interpret-mode concern), matching the
                            # VMEM accounting in stencil_spmv.py's docstring

#: production operating point for the stencil kernels (128² (y, z) planes)
#: and the grid extents a default x-slab must divide
PROD_PLANE = (128, 128)
PROD_NZ = (32, 64, 128)
#: flattened-row counts of the production grids for the (br, 1024)-tiled
#: vector kernels: 128³/1024 and 128·128·64/1024
PROD_ROWS = (1024, 2048)
#: grids used by tests/test_kernels.py (completeness cross-checks the file)
TEST_GRIDS = ((8, 8, 8), (12, 10, 16), (16, 16, 24))


def _slab_bytes(*, bz: int = 8, windows: int = 1, plains: int = 0,
                outs: int = 1, accs: int = 0,
                plane: tuple[int, int] = PROD_PLANE) -> int:
    """Footprint of one grid step of an x-slab stencil kernel: ``windows``
    halo-padded (bz+2, ny+2, nz+2) inputs, ``plains`` unpadded (bz, ny, nz)
    inputs, ``outs`` (bz, ny, nz) outputs, ``accs`` scalar accumulators."""
    ny, nz = plane
    win = (bz + 2) * (ny + 2) * (nz + 2)
    blk = bz * ny * nz
    one_step = windows * win + (plains + outs) * blk
    return _DOUBLE_BUFFER * _ITEMSIZE * one_step + accs * _ITEMSIZE


def _row_bytes(n_bufs: int, *, br: int = 256, row: int = 1024,
               accs: int = 0) -> int:
    """Footprint of one grid step of a flattened (br, ROW)-tiled vector
    kernel (fused_axpby/cg_fused_update family): ``n_bufs`` live in/out
    blocks plus scalar accumulators."""
    return _DOUBLE_BUFFER * _ITEMSIZE * n_bufs * br * row + accs * _ITEMSIZE


def _flash_bytes(*, bq: int = 256, bkv: int = 256, hd: int = 128) -> int:
    """One (bq × bkv) attention tile: q block, k/v blocks, logits/weights,
    online-softmax running stats + output accumulator."""
    tile = bq * hd + 2 * bkv * hd + bq * bkv + bq * hd + 2 * bq
    return _DOUBLE_BUFFER * 4 * tile    # attention runs in f32/bf16


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One audited kernel: wrapper name, where it lives, its oracle, and the
    static facts the checks run on."""

    name: str                      # public wrapper in repro.kernels.ops
    module: str                    # repro.kernels.<module> with the pallas_call
    ref: str                       # oracle fn in repro.kernels.ref
    vmem_bytes: int                # footprint estimate at production shape
    block_z: int | None = 8        # slab depth that must divide the grids below
    divides: tuple[int, ...] = PROD_NZ


KERNEL_TABLE: tuple[KernelSpec, ...] = (
    KernelSpec("spmv", "stencil_spmv", "stencil_spmv_ref",
               _slab_bytes(windows=1, outs=1)),
    KernelSpec("spmv_dot", "stencil_spmv", "stencil_spmv_dot_ref",
               _slab_bytes(windows=1, outs=1, accs=1)),
    KernelSpec("spmv_dots", "spmv_dot", "stencil_spmv_dots_ref",
               _slab_bytes(windows=1, outs=1, accs=2)),
    KernelSpec("cg_update", "cg_fused_update", "cg_fused_update_ref",
               _row_bytes(6, accs=1), block_z=256, divides=PROD_ROWS),
    KernelSpec("cg_body", "cg_fused_update", "fused_cg_body_ref",
               _row_bytes(9, br=128), block_z=128, divides=PROD_ROWS),
    KernelSpec("axpbypcz", "fused_axpby", "fused_axpby_ref",
               _row_bytes(4), block_z=256, divides=PROD_ROWS),
    KernelSpec("axpbypcz_dot", "fused_axpby", "fused_axpby_dot_ref",
               _row_bytes(5, accs=1), block_z=256, divides=PROD_ROWS),
    KernelSpec("gs_half_sweep", "rb_gs", "rb_gs_half_sweep_ref",
               _slab_bytes(windows=1, plains=1, outs=1)),
    KernelSpec("cheb_step", "precond", "cheb_fused_step_ref",
               _slab_bytes(windows=1, plains=2, outs=2)),
    KernelSpec("jacobi_sweep", "precond", "block_jacobi_sweep_ref",
               _slab_bytes(windows=1, plains=1, outs=1)),
    KernelSpec("flash_attention", "flash_attention", "flash_attention_ref",
               _flash_bytes(), block_z=256,
               divides=(1024, 2048, 4096)),
    # --- PR 10: the full fused-body family ----------------------------------
    KernelSpec("spmv_dots3", "spmv_dot", "stencil_spmv_dots3_ref",
               _slab_bytes(windows=1, plains=1, outs=1, accs=3)),
    KernelSpec("fused_dots", "fused_bodies", "fused_dots_ref",
               _row_bytes(3, accs=3), block_z=256, divides=PROD_ROWS),
    KernelSpec("pipe_body", "fused_bodies", "fused_pipe_body_ref",
               _row_bytes(13, br=64), block_z=64, divides=PROD_ROWS),
    KernelSpec("pcg_body", "fused_bodies", "fused_pcg_body_ref",
               _row_bytes(10, br=128), block_z=128, divides=PROD_ROWS),
    KernelSpec("ppipe_body", "fused_bodies", "fused_ppipe_body_ref",
               _row_bytes(18, br=64), block_z=64, divides=PROD_ROWS),
    KernelSpec("bicgstab_update1", "fused_bodies", "bicgstab_update1_ref",
               _row_bytes(9, br=128), block_z=128, divides=PROD_ROWS),
    KernelSpec("bicgstab_spmv_dots", "bicgstab_fused",
               "bicgstab_spmv_dots_ref",
               _slab_bytes(windows=1, plains=6, outs=3, accs=9)),
    KernelSpec("bicgstab_spmv_update", "bicgstab_fused",
               "bicgstab_spmv_update_ref",
               _slab_bytes(windows=1, plains=6, outs=4)),
)

#: public names in kernels.ops that deliberately have no table row
_EXEMPT_WRAPPERS = {
    # thin factory closing over `spmv` (audited above) — no kernel of its own
    "make_matvec_padded",
    # the float64-on-TPU guard the session calls — runs no kernel
    "check_dtype",
}

#: kernel modules that hold no kernel of their own: ``blocks`` is the
#: ``pallas_call`` wrapper every kernel module above goes through
_EXEMPT_MODULES = {"__init__", "blocks"}


def _kernels_dir() -> pathlib.Path:
    import repro.kernels
    return pathlib.Path(repro.kernels.__file__).resolve().parent


def _tests_file() -> pathlib.Path:
    # src/repro/kernels -> repo root / tests/test_kernels.py
    return _kernels_dir().parents[2] / "tests" / "test_kernels.py"


def check_kernels(table: tuple[KernelSpec, ...] | None = None, *,
                  budget: int = VMEM_BUDGET_BYTES) -> list[Violation]:
    """Run every kernel static check; returns the (possibly empty) findings."""
    table = KERNEL_TABLE if table is None else table
    out: list[Violation] = []

    from repro.kernels import ops as ops_mod, ref as ref_mod

    # --- VMEM budget + divisibility per row ---------------------------------
    for spec in table:
        subj = f"kernel:{spec.name}"
        if spec.vmem_bytes > budget:
            out.append(Violation(
                "lint_kernels", subj, "vmem_bytes",
                expected=f"<= {budget} (VMEM budget)",
                actual=spec.vmem_bytes,
                detail="block shape streams more than VMEM per grid step"))
        if spec.block_z:
            bad = [n for n in spec.divides if n % spec.block_z]
            if bad:
                out.append(Violation(
                    "lint_kernels", subj, "block_divisibility",
                    expected=f"block {spec.block_z} divides grid depths "
                             f"{spec.divides}",
                    actual=f"non-dividing depths {bad}",
                    detail="slab_depth would silently shrink the block "
                           "(perf cliff)"))

    # --- completeness: wrapper, oracle, test row ----------------------------
    try:
        tests_src = _tests_file().read_text()
    except OSError:
        tests_src = None
        out.append(Violation(
            "lint_kernels", "kernel:*", "test_row",
            expected=f"readable {_tests_file()}",
            actual="missing", detail="cannot verify per-kernel test rows"))
    for spec in table:
        subj = f"kernel:{spec.name}"
        if not callable(getattr(ops_mod, spec.name, None)):
            out.append(Violation(
                "lint_kernels", subj, "wrapper",
                expected=f"repro.kernels.ops.{spec.name}", actual="missing"))
        if not callable(getattr(ref_mod, spec.ref, None)):
            out.append(Violation(
                "lint_kernels", subj, "oracle",
                expected=f"repro.kernels.ref.{spec.ref}", actual="missing",
                detail="every kernel needs a pure-jnp allclose reference"))
        if tests_src is not None and f"ops.{spec.name}" not in tests_src:
            out.append(Violation(
                "lint_kernels", subj, "test_row",
                expected=f"'ops.{spec.name}' referenced in "
                         f"tests/test_kernels.py",
                actual="no reference",
                detail="kernel has no interpret-mode row against its oracle"))

    # --- completeness: every pallas_call module covered, every public
    # wrapper tabled (only for the default table — an injected test table is
    # deliberately partial) --------------------------------------------------
    if table is KERNEL_TABLE:
        covered = {spec.module for spec in table}
        for py in sorted(_kernels_dir().glob("*.py")):
            if py.stem in _EXEMPT_MODULES:
                continue
            if "pallas_call" in py.read_text() and py.stem not in covered:
                out.append(Violation(
                    "lint_kernels", f"kernel:{py.stem}", "table_row",
                    expected="a KERNEL_TABLE row per pallas_call module",
                    actual="module not covered",
                    detail=str(py)))
        tabled = {spec.name for spec in table} | _EXEMPT_WRAPPERS
        for name, fn in inspect.getmembers(ops_mod, inspect.isfunction):
            if name.startswith("_") or fn.__module__ != ops_mod.__name__:
                continue
            if name not in tabled:
                out.append(Violation(
                    "lint_kernels", f"kernel:{name}", "table_row",
                    expected="a KERNEL_TABLE row per public kernel wrapper",
                    actual="wrapper not covered"))
        # every fused hook a method declares must itself be a tabled kernel —
        # an untabled (hence oracle-less, VMEM-unchecked) kernel reached via
        # the fused path would dodge all the checks above
        from repro.core.methods import METHODS
        for mname, mdef in sorted(METHODS.items()):
            for hook in mdef.fused_kernels:
                if hook not in tabled:
                    out.append(Violation(
                        "lint_kernels", f"kernel:{hook}", "fused_coverage",
                        expected=f"a KERNEL_TABLE row for fused hook "
                                 f"{hook!r} (declared by {mname!r})",
                        actual="hook not tabled",
                        detail="fused-path kernels take the same "
                               "VMEM/oracle/test checks as classic ones"))
    return out

"""Persistent autotuner for the Pallas kernel tilings and the XLA crossover.

The fused kernels have two free tiling knobs — ``bz`` (slab depth of the
stencil kernels) and ``br`` (row-block of the flattened vector-update
kernels) — plus one *routing* decision: below a crossover volume the
per-kernel dispatch overhead makes the separately-launched Pallas path
slower than letting XLA fuse the whole jitted iteration (the measured 16³
case where ``cg_classic_kernels`` ran 3.5× behind ``cg_classic_jit``).

``sweep`` measures all three per ``(stencil, grid, dtype, device_kind)``
and ``tune`` persists the winner in a JSON cache (same key discipline as
the serve executable cache: exact shapes, no fuzzy matching).  ``resolve``
is the read side consulted by ``PallasOp`` (tile sizes) and
``SolverSession`` (``options.pallas = None`` → the routing bit); a cache
miss falls back to the static default table below, so nothing ever
*requires* a tuning run:

  default table
  -------------
  use_pallas :  backend == "tpu"  AND  dtype is not float64
                AND  nx·ny·nz >= MIN_PALLAS_VOLUME (24³)
  bz         :  8   x-planes per slab (shrunk per shape by
                ``stencil_spmv.slab_depth`` to divide nx and fit VMEM)
  br         :  None (each kernel's own VMEM-budgeted default)

Cache file: ``$REPRO_AUTOTUNE_CACHE`` or ``~/.cache/repro/autotune.json``.
CLI: ``python -m repro.kernels.autotune --grid 32 32 32 [--retune]``;
``--smoke`` runs the two bounded CI configs (see ``make autotune-smoke``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

DEFAULT_BZ = 8
MIN_PALLAS_VOLUME = 24 ** 3   # below this, XLA whole-iteration fusion wins
BZ_CANDIDATES = (4, 8, 16)
BR_CANDIDATES = (64, 128, 256)

_DTYPES = {"float32": jnp.float32, "float64": jnp.float64,
           "bfloat16": jnp.bfloat16}


@dataclasses.dataclass(frozen=True)
class TuneDecision:
    """What the kernel layer should do at one (stencil, grid, dtype) point.

    ``br = None`` keeps each row-tiled kernel's own VMEM-budgeted default;
    a tuned value overrides only the merged-CG/PCG body family the sweep
    actually measures.  ``source`` is ``"default"`` (static table) or
    ``"cache"`` (a persisted tuning run) — surfaced in telemetry/bench so
    a silent fallback is visible.
    """

    use_pallas: bool
    bz: int = DEFAULT_BZ
    br: int | None = None
    source: str = "default"


def device_kind() -> str:
    return jax.devices()[0].device_kind


def tune_key(stencil: str, grid, dtype, kind: str | None = None) -> str:
    nx, ny, nz = grid
    kind = device_kind() if kind is None else kind
    return f"{stencil}|{nx}x{ny}x{nz}|{jnp.dtype(dtype).name}|{kind}"


def cache_path() -> Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "autotune.json"


_CACHE: tuple[Path, float, dict] | None = None


def load_cache(path: Path | None = None) -> dict:
    """The persisted tune table, memoized on (path, mtime)."""
    global _CACHE
    path = cache_path() if path is None else Path(path)
    try:
        mtime = path.stat().st_mtime
    except OSError:
        return {}
    if _CACHE is not None and _CACHE[0] == path and _CACHE[1] == mtime:
        return _CACHE[2]
    try:
        table = json.loads(path.read_text())
    except (OSError, ValueError):
        return {}
    _CACHE = (path, mtime, table)
    return table


def save_cache(table: dict, path: Path | None = None) -> Path:
    global _CACHE
    path = cache_path() if path is None else Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(table, indent=2, sort_keys=True) + "\n")
    _CACHE = None
    return path


def default_decision(grid, *, backend: str | None = None,
                     dtype=jnp.float32) -> TuneDecision:
    """The documented static fallback (no cache entry, no tuning run).
    f64 stays on XLA: the TPU's kernel compiler lowers no 64-bit floats."""
    backend = jax.default_backend() if backend is None else backend
    nx, ny, nz = grid
    on = (backend == "tpu" and jnp.dtype(dtype) != jnp.float64
          and nx * ny * nz >= MIN_PALLAS_VOLUME)
    return TuneDecision(use_pallas=on)


def resolve(stencil: str, grid, dtype, *,
            path: Path | None = None) -> TuneDecision:
    """Cache lookup with default-table fallback (the PallasOp/session read)."""
    entry = load_cache(path).get(tune_key(stencil, grid, dtype))
    if entry is None:
        return default_decision(grid, dtype=dtype)
    return TuneDecision(use_pallas=bool(entry["use_pallas"]),
                        bz=int(entry["bz"]),
                        br=None if entry.get("br") is None else int(entry["br"]),
                        source="cache")


# ---------------------------------------------------------------- measurement

def _timeit(fn, *args, repeats: int = 3) -> float:
    """min-of-repeats wall seconds for fn(*args) (compile excluded)."""
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def sweep(grid, stencil: str = "7pt", dtype=jnp.float32, *,
          repeats: int = 3) -> dict:
    """Measure bz/br winners and the Pallas-vs-XLA crossover at one point.

    Returns a JSON-ready cache entry.  Off-TPU the Pallas timings are the
    ``interpret=True`` path — honest for the routing bit (interpret mode
    *should* lose to XLA), meaningless as absolute kernel throughput; the
    entry records ``backend`` so a cache tuned on one device kind is never
    mistaken for another (the key already pins ``device_kind``).
    """
    from repro.core.problems import make_problem
    from repro.kernels import ops, ref

    prob = make_problem(tuple(grid), stencil)
    st = prob.stencil
    nx, ny, nz = grid
    key = jax.random.PRNGKey(0)
    r = jax.random.normal(key, (nx, ny, nz), dtype)
    xp = jnp.pad(r, 1)
    vecs = [jax.random.normal(jax.random.fold_in(key, i), (nx, ny, nz), dtype)
            for i in range(5)]
    alpha = jnp.asarray(0.5, dtype)
    beta = jnp.asarray(0.1, dtype)

    # -- bz: the slab SpMV+dots kernel, all candidates that divide nz
    bz_times = {}
    for bz in BZ_CANDIDATES:
        if nz % bz:
            continue
        bz_times[bz] = _timeit(
            lambda b=bz: ops.spmv_dots(xp, st, bz=b), repeats=repeats)
    best_bz = min(bz_times, key=bz_times.get) if bz_times else DEFAULT_BZ

    # -- br: the row-tiled merged-body kernel
    br_times = {}
    for br in BR_CANDIDATES:
        br_times[br] = _timeit(
            lambda b=br: ops.cg_body(alpha, beta, *vecs[:4], r, br=b),
            repeats=repeats)
    best_br = min(br_times, key=br_times.get)

    # -- crossover: separately-dispatched Pallas pass vs whole-jit XLA ref
    pallas_t = _timeit(lambda: ops.spmv_dots(xp, st, bz=best_bz),
                       repeats=repeats)
    xla = jax.jit(lambda a: ref.stencil_spmv_dots_ref(a, stencil=st))
    xla_t = _timeit(xla, xp, repeats=repeats)

    return {
        "use_pallas": bool(pallas_t <= xla_t),
        "bz": int(best_bz),
        "br": int(best_br),
        "backend": jax.default_backend(),
        "timings": {
            "bz": {str(k): v for k, v in bz_times.items()},
            "br": {str(k): v for k, v in br_times.items()},
            "pallas_s": pallas_t,
            "xla_s": xla_t,
        },
    }


def tune(grid, stencil: str = "7pt", dtype=jnp.float32, *,
         path: Path | None = None, retune: bool = False,
         repeats: int = 3) -> TuneDecision:
    """Sweep-and-persist (skipped if already cached, unless ``retune``)."""
    key = tune_key(stencil, grid, dtype)
    table = dict(load_cache(path))
    if key not in table or retune:
        table[key] = sweep(grid, stencil, dtype, repeats=repeats)
        save_cache(table, path)
    return resolve(stencil, grid, dtype, path=path)


SMOKE_CONFIGS = (((16, 16, 16), "7pt"), ((32, 32, 32), "7pt"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--grid", type=int, nargs=3, default=(32, 32, 32))
    ap.add_argument("--stencil", choices=("7pt", "27pt"), default="7pt")
    ap.add_argument("--dtype", choices=sorted(_DTYPES), default="float32")
    ap.add_argument("--cache", type=Path, default=None,
                    help="cache file (default: $REPRO_AUTOTUNE_CACHE)")
    ap.add_argument("--retune", action="store_true",
                    help="re-measure even if the key is already cached")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--smoke", action="store_true",
                    help="bounded sweep over the two CI configs")
    args = ap.parse_args(argv)

    configs = (SMOKE_CONFIGS if args.smoke
               else (((tuple(args.grid)), args.stencil),))
    for grid, stencil in configs:
        dec = tune(grid, stencil, _DTYPES[args.dtype], path=args.cache,
                   retune=args.retune, repeats=args.repeats)
        print(f"{tune_key(stencil, grid, _DTYPES[args.dtype])}: "
              f"use_pallas={dec.use_pallas} bz={dec.bz} br={dec.br} "
              f"[{dec.source}]")
    print(f"cache: {args.cache or cache_path()}")
    return 0


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())

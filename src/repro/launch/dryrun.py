import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run (deliverable e): lower + compile EVERY
(architecture × input shape × mesh) cell on 512 placeholder host devices,
print ``memory_analysis()`` (proves it fits) and ``cost_analysis()``
(feeds §Roofline), and dump a JSON record per cell under experiments/dryrun/.

Per-cell record:
  * bytes per device (argument/output/temp/peak) from memory_analysis,
  * HLO flops / bytes, raw and trip-count-corrected (scan bodies appear once
    in HLO; a single-layer compile supplies the per-layer cost, DESIGN.md §7),
  * collective operand bytes by op kind (all-gather/all-reduce/
    reduce-scatter/all-to-all/collective-permute), trip-scaled,
  * the analytic MODEL_FLOPS (6·N·D train / 2·N·D decode) for the
    useful-compute ratio.

Usage:
  python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every LM cell, both meshes
  python -m repro.launch.dryrun --solvers        # the paper's HPCG cells
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_bytes, count_collectives
from repro.configs.base import SHAPES, all_configs, get_config
from repro.distributed.sharding import (
    batch_shardings,
    dp_axes_of,
    param_shardings,
)
from repro.launch.mesh import make_production_mesh
from repro.models import steps as steps_mod
from repro.models.transformer import ModelCtx, init_params
from repro.optim.adamw import adamw
from repro.optim.schedules import for_arch

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mesh(kind: str):
    return make_production_mesh(multi_pod=(kind == "multi"))


def _n_chips(mesh) -> int:
    return mesh.devices.size


def _ctx(cfg, mesh, profile: str = "tp") -> ModelCtx:
    """profile: "tp" (baseline TP+SP) | "fsdp" (batch over both axes; the
    recommended layout for small-d archs — EXPERIMENTS.md §Perf-2b)."""
    if profile == "fsdp":
        dp = tuple(a for a in ("pod", "data", "model")
                   if a in mesh.axis_names)
    else:
        dp = dp_axes_of(mesh)
    return ModelCtx(cfg=cfg, mesh=mesh, dp_axes=dp,
                    tp_axis="model", dtype=jnp.bfloat16, remat=True)


def _trips(cfg) -> int:
    if cfg.family == "moe" and cfg.moe_every == 2:
        return cfg.n_layers // 2
    if cfg.local_global:
        return cfg.n_layers // 2
    return cfg.n_layers


# -----------------------------------------------------------------------------
# Single-layer cost probes (trip-count correction)
# -----------------------------------------------------------------------------

def _layer_cost(ctx, params_shape, batch, kind: str):
    """cost_analysis of ONE scanned-group body (fwd, and fwd+bwd for train)."""
    from repro.models.transformer import _layer_forward, layer_kind
    cfg = ctx.cfg
    mesh = ctx.mesh
    dp = dp_axes_of(mesh)
    dp_spec = dp if len(dp) > 1 else dp[0]

    layer_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
        params_shape["layers"])

    ref = batch.get("tokens", batch.get("embeds"))
    B, S = ref.shape[:2]
    h_shape = jax.ShapeDtypeStruct((B, S, cfg.d_model), ctx.dtype)
    tp = mesh.shape["model"]
    sp_ok = S % tp == 0
    pos = batch["positions"]

    def group_fwd(lp, h, positions):
        if cfg.family == "moe" and cfg.moe_every == 2:
            h, _ = _layer_forward(ctx, lp["dense"], h, positions, window=0,
                                  kind="dense")
            h, _ = _layer_forward(ctx, lp["moe"], h, positions, window=0,
                                  kind="moe")
            return h
        if cfg.local_global:
            p0 = jax.tree.map(lambda x: x, lp)
            h, _ = _layer_forward(ctx, p0, h, positions,
                                  window=cfg.sliding_window, kind="dense")
            h, _ = _layer_forward(ctx, p0, h, positions, window=0, kind="dense")
            return h
        h, _ = _layer_forward(ctx, lp, h, positions, window=cfg.sliding_window,
                              kind=layer_kind(cfg))
        return h

    # reuse the global param rules minus the leading layer axis
    from repro.distributed.sharding import param_specs
    full_specs = param_specs(params_shape, ctx.mesh)["layers"]
    lp_shard = jax.tree.map(
        lambda spec: NamedSharding(ctx.mesh, P(*spec[1:])), full_specs)
    # match the scan steady state: the residual stream is sequence-parallel
    h_shard = NamedSharding(ctx.mesh, P(dp_spec, "model" if sp_ok else None,
                                        None))
    pos_shard = batch_shardings({"positions": pos}, ctx.mesh)["positions"]

    if kind == "train":
        def fwd_loss(lp, h, positions):
            return jnp.sum(group_fwd(lp, h, positions).astype(jnp.float32))

        fn = jax.jit(jax.grad(fwd_loss, argnums=(0, 1)),
                     in_shardings=(lp_shard, h_shard, pos_shard))
    else:
        fn = jax.jit(group_fwd, in_shardings=(lp_shard, h_shard, pos_shard))
    compiled = fn.lower(layer_shapes, h_shape, pos).compile()
    ca = compiled.cost_analysis()
    cb = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collective_bytes": float(cb)}


def _decode_layer_cost(ctx, params_shape, batch):
    """One decode-group body cost (caches included)."""
    from repro.models.decode import _decode_layer
    from repro.models.transformer import layer_kind
    cfg = ctx.cfg
    mesh = ctx.mesh
    dp = dp_axes_of(mesh)
    dp_spec = dp if len(dp) > 1 else dp[0]
    B = batch["tokens"].shape[0]

    layer_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
        params_shape["layers"])
    cache_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
        batch["caches"])
    h_shape = jax.ShapeDtypeStruct((B, 1, cfg.d_model), ctx.dtype)
    pos_shape = jax.ShapeDtypeStruct((), jnp.int32)

    def group(lp, h, cur_pos, cache):
        if cfg.family == "moe" and cfg.moe_every == 2:
            h, cd = _decode_layer(ctx, lp["dense"], h, cur_pos,
                                  {"attn": cache["dense"]}, window=0, kind="dense")
            h, cm = _decode_layer(ctx, lp["moe"], h, cur_pos,
                                  {"attn": cache["moe"]}, window=0, kind="moe")
            return h, {"dense": cd["attn"], "moe": cm["attn"]}
        if cfg.local_global:
            h, ce = _decode_layer(ctx, lp, h, cur_pos, {"attn": cache["even"]},
                                  window=cfg.sliding_window, kind="dense")
            h, co = _decode_layer(ctx, lp, h, cur_pos, {"attn": cache["odd"]},
                                  window=0, kind="dense")
            return h, {"even": ce["attn"], "odd": co["attn"]}
        h, nc = _decode_layer(ctx, lp, h, cur_pos, cache,
                              window=cfg.sliding_window, kind=layer_kind(cfg))
        return h, nc

    # shard the probe's inputs like the real step (a replicated cache would
    # inflate the probe's per-device bytes by the full cache size)
    from repro.distributed.sharding import param_specs
    full_specs = param_specs(params_shape, ctx.mesh, cfg)["layers"]
    lp_shard = jax.tree.map(
        lambda spec: NamedSharding(ctx.mesh, P(*spec[1:])), full_specs)

    def cache_spec(path, x):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("k", "v") or name == "state":   # (B,C,KV,hd)/(B,nH,P,N)
            return NamedSharding(ctx.mesh, P(dp_spec, "model", None, None))
        if name == "conv":                          # (B,K-1,ch)
            return NamedSharding(ctx.mesh, P(dp_spec, None, None))
        return NamedSharding(ctx.mesh, P())         # pos

    cache_shard = jax.tree_util.tree_map_with_path(cache_spec, cache_shapes)
    h_shard = NamedSharding(ctx.mesh, P(dp_spec, None, None))
    fn = jax.jit(group, in_shardings=(lp_shard, h_shard, None, cache_shard))
    compiled = fn.lower(layer_shapes, h_shape, pos_shape, cache_shapes).compile()
    ca = compiled.cost_analysis()
    cb = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collective_bytes": float(cb)}


# -----------------------------------------------------------------------------
# LM cells
# -----------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             profile: str = "tp", verbose: bool = True) -> dict:
    cfg = get_config(arch)
    mesh = _mesh(mesh_kind)
    ctx = _ctx(cfg, mesh, profile)
    S, B, kind = SHAPES[shape_name]
    t0 = time.time()

    params_shape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    p_shard = param_shardings(params_shape, mesh, cfg)
    batch = steps_mod.input_specs(cfg, shape_name)
    b_shard = batch_shardings(batch, mesh, ctx.dp_axes)

    if kind == "train":
        opt = adamw(for_arch(arch, 3e-4, 10_000))
        opt_shape = jax.eval_shape(opt.init, params_shape)
        from repro.distributed.sharding import opt_state_specs, param_specs
        o_specs = opt_state_specs(opt_shape, param_specs(params_shape, mesh))
        o_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs)
        step = steps_mod.make_train_step(ctx, opt)

        def train_nometrics(params, opt_state, batch):
            p2, o2, _, m = step(params, opt_state, None, batch)
            return p2, o2, m

        fn = jax.jit(
            train_nometrics,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        )
        lowered = fn.lower(params_shape, opt_shape, batch)
    elif kind == "prefill":
        fwd = steps_mod.make_prefill(ctx)
        fn = jax.jit(fwd, in_shardings=(p_shard, b_shard))
        lowered = fn.lower(params_shape, batch)
    else:  # decode
        dstep = steps_mod.make_decode_step(ctx)
        cache_shard = b_shard["caches"]
        if cfg.enc_dec:
            fn = jax.jit(dstep, in_shardings=(
                p_shard, b_shard["tokens"], b_shard["cur_pos"], cache_shard,
                b_shard["cross_kvs"]),
                out_shardings=(None, cache_shard),
                donate_argnums=(3,))
            lowered = fn.lower(params_shape, batch["tokens"], batch["cur_pos"],
                               batch["caches"], batch["cross_kvs"])
        else:
            fn = jax.jit(dstep, in_shardings=(
                p_shard, b_shard["tokens"], b_shard["cur_pos"], cache_shard),
                out_shardings=(None, cache_shard),
                donate_argnums=(3,))
            lowered = fn.lower(params_shape, batch["tokens"], batch["cur_pos"],
                               batch["caches"])

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    n_coll = count_collectives(hlo)
    cb_raw = collective_bytes(hlo)
    trips = _trips(cfg)

    # trip-count correction via single-group probes
    try:
        if kind == "decode":
            layer = _decode_layer_cost(ctx, params_shape, batch)
        else:
            layer = _layer_cost(ctx, params_shape, batch, kind)
    except Exception as e:  # noqa: BLE001 — correction is best-effort
        layer = {"flops": 0.0, "bytes": 0.0, "collective_bytes": 0.0,
                 "error": f"{type(e).__name__}: {e}"}

    enc_trips = cfg.n_enc_layers if cfg.enc_dec and kind != "decode" else 0
    mult = trips - 1 + enc_trips  # encoder bodies approximated by the decoder probe
    flops = float(ca.get("flops", 0.0)) + mult * layer["flops"]
    bytes_ = float(ca.get("bytes accessed", 0.0)) + mult * layer["bytes"]
    coll = cb_raw + mult * layer["collective_bytes"]

    n_tok = S * B
    N = cfg.active_param_count()
    if kind == "train":
        model_flops = 6 * N * n_tok
    elif kind == "prefill":
        model_flops = 2 * N * n_tok
    else:
        model_flops = 2 * N * B  # one token per sequence

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": _n_chips(mesh), "kind": kind,
        "seq_len": S, "batch": B,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "hlo_flops_raw": float(ca.get("flops", 0.0)),
        "hlo_bytes_raw": float(ca.get("bytes accessed", 0.0)),
        "layer_probe": layer,
        "trips": trips,
        "hlo_flops": flops,
        "hlo_bytes": bytes_,
        "collective_bytes": coll,
        "collective_counts": n_coll,
        "model_flops": float(model_flops),
        "compile_s": round(time.time() - t0, 1),
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_kind}: "
              f"compile {rec['compile_s']}s, "
              f"flops {flops:.3e}, coll {coll:.3e} B, "
              f"collectives {n_coll}")
        print(f"  memory_analysis: {rec['memory']}")
    return rec


# -----------------------------------------------------------------------------
# Solver cells (the paper's workload on the production mesh)
# -----------------------------------------------------------------------------

def run_solver_cell(method: str, stencil: str, mesh_kind: str, *,
                    local_grid=(128, 128, 128), verbose=True) -> dict:
    from repro.api import SolverOptions, SolverSession, resolve_backend
    from repro.core.problems import make_problem

    mesh = _mesh(mesh_kind)
    opts = SolverOptions(f64=False)
    backend = resolve_backend(opts, mesh=mesh)
    gshape = tuple(local_grid[d] * backend.layout.axis_size(d)
                   for d in range(3))
    prob = make_problem(gshape, stencil, dtype=jnp.float32)
    t0 = time.time()
    sess = SolverSession(prob, method=method, options=opts, backend=backend)
    fn, layout = sess.step_fn()
    spec = layout.spec()
    sh = NamedSharding(mesh, spec)
    arr = jax.ShapeDtypeStruct(gshape, jnp.float32, sharding=sh)
    scal = jax.ShapeDtypeStruct((), jnp.float32)
    # the step state is method-dependent (the reduction-hiding variants
    # carry more recurrence vectors than the classic 4-slot layout)
    from repro.core.distributed import step_state_layout
    vec_names, scal_names = step_state_layout(method)
    args = [arr] * (1 + len(vec_names)) + [scal] * len(scal_names)
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    rec = {
        "method": method, "stencil": stencil, "mesh": mesh_kind,
        "chips": _n_chips(mesh), "global_grid": gshape,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "hlo_flops": float(ca.get("flops", 0.0)),
        "hlo_bytes": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes": float(collective_bytes(hlo)),
        "collective_counts": count_collectives(hlo),
        "compile_s": round(time.time() - t0, 1),
    }
    if verbose:
        print(f"[dryrun] hpcg-{method}-{stencil} × {mesh_kind}: "
              f"compile {rec['compile_s']}s, collectives "
              f"{rec['collective_counts']}, coll bytes {rec['collective_bytes']:.3e}")
    return rec


# -----------------------------------------------------------------------------
# CLI
# -----------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--solvers", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--profile", default="tp", choices=["tp", "fsdp"])
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    cells: list[tuple[str, str]] = []
    if args.all:
        for name, cfg in sorted(all_configs().items()):
            for shape in cfg.shapes():
                cells.append((name, shape))
    elif args.arch:
        cfg = get_config(args.arch)
        shapes = [args.shape] if args.shape else list(cfg.shapes())
        cells = [(args.arch, s) for s in shapes]

    for arch, shape in cells:
        for mk in meshes:
            tag = f"{arch}_{shape}_{mk}"
            if args.profile != "tp":
                tag += f"_{args.profile}"
            try:
                rec = run_cell(arch, shape, mk, profile=args.profile)
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
            except Exception:
                failures.append(tag)
                traceback.print_exc()

    if args.solvers:
        # every registered method — the registry is the single source; new
        # MethodDefs show up here (and in the benchmarks) automatically
        from repro.api.registry import solver_names
        for method in solver_names():
            for stencil in ("7pt", "27pt"):
                for mk in meshes:
                    tag = f"hpcg-{method}-{stencil}_{mk}"
                    try:
                        rec = run_solver_cell(method, stencil, mk)
                        with open(os.path.join(args.out, tag + ".json"), "w") as f:
                            json.dump(rec, f, indent=1)
                    except Exception:
                        failures.append(tag)
                        traceback.print_exc()

    if failures:
        print(f"[dryrun] FAILURES ({len(failures)}): {failures}")
        raise SystemExit(1)
    print("[dryrun] all cells compiled OK")


if __name__ == "__main__":
    main()

"""Serving drivers behind one CLI: ``--mode solver`` (default ``lm``).

``lm``     — batched LM decode demo: prefill once, then autoregressive
             decode (CPU-scale demo of the decode_32k/long_500k dry-run
             cells).
``solver`` — the production solver service (``repro.serve``): replay a
             mixed-workload trace through continuous batching over the
             compiled-executable cache, print SLO metrics, optionally
             inject a preemption to exercise the WAL recovery path.

PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --reduced \
    --batch 4 --prompt-len 64 --gen 32

PYTHONPATH=src python -m repro.launch.serve --mode solver --scale 2 \
    --fail-at 3 --json
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

# enc-dec serving reuses the decoder path with precomputed cross-kv; the
# frontend stub provides source embeddings.


def _lm_main(args) -> dict:
    from repro.configs.base import get_config
    from repro.distributed.sharding import dp_axes_of
    from repro.launch.mesh import make_mesh_for_devices
    from repro.models import steps as steps_mod
    from repro.models.decode import caches_from_prefill, init_caches
    from repro.models.transformer import ModelCtx, init_params

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dtype = jnp.dtype(args.dtype)
    mesh = make_mesh_for_devices()
    ctx = ModelCtx(cfg=cfg, mesh=mesh, dp_axes=dp_axes_of(mesh),
                   dtype=dtype, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype)
    B, P = args.batch, args.prompt_len
    cache_len = P + args.gen

    key = jax.random.PRNGKey(1)
    prompt = jax.random.randint(key, (B, P), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": prompt,
             "positions": jnp.broadcast_to(jnp.arange(P), (B, P))}
    if cfg.mrope:
        batch["positions"] = jnp.broadcast_to(batch["positions"][None],
                                              (3, B, P))
    if cfg.enc_dec:
        T = max(P // steps_mod.SRC_FRACTION, 1)
        batch["src_embeds"] = jax.random.normal(key, (B, T, cfg.d_model), dtype)
        batch["src_positions"] = jnp.broadcast_to(jnp.arange(T), (B, T))

    # --- prefill ------------------------------------------------------------
    from repro.models.transformer import forward_hidden, logits_from_h
    t0 = time.time()
    h, extras = jax.jit(
        lambda p, b: forward_hidden(ctx, p, b, collect_kv=True)
    )(params, batch)
    logits = logits_from_h(ctx, params, h[:, -1:])
    if cfg.family in ("ssm", "hybrid"):
        # SSD state is rebuilt by replay for the demo (prefill-state plumbing
        # for hybrid archs is decode-from-scratch; see DESIGN.md §4)
        caches = init_caches(ctx, B, cache_len)
        cross = None
        tok = prompt[:, :1]
        dstep = jax.jit(steps_mod.make_decode_step(ctx))
        for i in range(P):
            logits, caches = dstep(params, prompt[:, i:i + 1],
                                   jnp.array(i, jnp.int32), caches)
    elif cfg.enc_dec:
        caches_built, cross = caches_from_prefill(ctx, extras["kvs"], cache_len)
        caches = caches_built
        # cross kv stacked per layer: (k, v) each (L, B, T, KV, hd)
        dstep = jax.jit(steps_mod.make_decode_step(ctx))
    else:
        caches = caches_from_prefill(ctx, extras["kvs"], cache_len)
        cross = None
        dstep = jax.jit(steps_mod.make_decode_step(ctx))
    t_prefill = time.time() - t0

    # --- decode loop ----------------------------------------------------------
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    out_tokens = [tok]
    t0 = time.time()
    for i in range(args.gen):
        pos = jnp.array(P + i, jnp.int32)
        if cfg.enc_dec:
            logits, caches = dstep(params, tok, pos, caches, cross)
        else:
            logits, caches = dstep(params, tok, pos, caches)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    gen = jnp.concatenate(out_tokens, axis=1)
    tps = B * args.gen / max(t_decode, 1e-9)
    print(f"[serve] arch={cfg.name} batch={B} prompt={P} gen={args.gen} "
          f"prefill {t_prefill:.2f}s decode {t_decode:.2f}s "
          f"({tps:.1f} tok/s)")
    print(f"[serve] sample continuation ids: {gen[0, :16].tolist()}")
    return {"tokens": gen, "tokens_per_s": tps}


def _solver_main(args) -> dict:
    from repro.core.problems import enable_f64
    from repro.runtime.monitor import FailureInjector
    from repro.serve import (MIXED_BUCKETS, SMOKE_BUCKETS, ServeConfig,
                             SolverService, generate_trace, replay)

    if args.trace:
        from repro.obs import trace as obs
        obs.enable(args.trace)   # equivalent: REPRO_TRACE=PATH
    enable_f64()   # the reference trace solves in the paper's f64
    cfg = ServeConfig(max_batch=args.max_batch,
                      cache_capacity=args.cache_capacity,
                      async_compile=not args.sync_compile,
                      recovery_dir=args.recovery_dir)
    injector = (FailureInjector(args.fail_at)
                if args.fail_at is not None else None)
    service = SolverService(cfg, injector=injector)
    recovered = service.recover()
    if recovered:
        print(f"[serve] recovered {len(recovered)} orphaned request(s) "
              f"from {cfg.recovery_dir}")
    buckets = SMOKE_BUCKETS if args.buckets == "smoke" else MIXED_BUCKETS
    trace = generate_trace(buckets, seed=args.seed, scale=args.scale)
    results = replay(service, trace)
    service.close()
    snap = service.snapshot()

    n_buckets = len({r.key() for r in trace})
    print(f"[serve] mode=solver: {len(results)}/{len(trace)} requests over "
          f"{n_buckets} buckets  max_batch={cfg.max_batch} "
          f"cache_capacity={cfg.cache_capacity}")
    print(f"[serve] qps={snap['qps']:.2f}  p50={snap['p50_s']*1e3:.0f}ms  "
          f"p95={snap['p95_s']*1e3:.0f}ms  p99={snap['p99_s']*1e3:.0f}ms  "
          f"preemptions={snap['preemptions']} requeued={snap['requeued']}")
    c = snap["cache"]
    print(f"[serve] cache: hits={c['hits']} misses={c['misses']} "
          f"evictions={c['evictions']} entries={c['entries']}")
    for b, st in c["per_bucket"].items():
        print(f"    {b}: compiles={st['misses']} "
              f"compile_s={st['compile_s']:.2f} batches={st['hits']}")
    out = {"mode": "solver", "requests": len(trace),
           "completed": len(results), "dropped": len(trace) - len(results),
           **{k: v for k, v in snap.items() if k != "t"}}
    if args.json:
        print(json.dumps(out))
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "solver"), default="lm",
                    help="lm = batched decode demo; solver = the repro.serve "
                         "solver service replaying a mixed trace")
    # -- lm mode ---------------------------------------------------------------
    ap.add_argument("--arch", default=None, help="(lm) model config name")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--dtype", default="float32")
    # -- solver mode -----------------------------------------------------------
    ap.add_argument("--scale", type=int, default=1,
                    help="(solver) trace size multiplier per bucket")
    ap.add_argument("--buckets", choices=("mixed", "smoke"), default="mixed",
                    help="(solver) reference mix to replay: mixed = the "
                         "acceptance trace, smoke = the tiny CI workload")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="(solver) append repro.obs/v1 records (serve "
                         "lifecycle spans + SLO events) to PATH; equivalent "
                         "to REPRO_TRACE=PATH")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="(solver) padded in-flight batch size per bucket")
    ap.add_argument("--cache-capacity", type=int, default=8,
                    help="(solver) LRU bound on resident executables")
    ap.add_argument("--sync-compile", action="store_true",
                    help="(solver) compile inline instead of a background "
                         "thread")
    ap.add_argument("--recovery-dir", default=None,
                    help="(solver) write-ahead journal dir (enables "
                         "preemption recovery)")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="(solver) inject a preemption at dispatch N "
                         "(exercises the recovery path)")
    ap.add_argument("--json", action="store_true",
                    help="(solver) also print the metrics record as JSON")
    args = ap.parse_args(argv)

    if args.mode == "solver":
        return _solver_main(args)
    if args.arch is None:
        ap.error("--arch is required for --mode lm")
    return _lm_main(args)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

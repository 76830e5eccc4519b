"""Mixture-of-Experts with explicit expert parallelism via shard_map.

TPU adaptation of the paper's over-decomposition idea applied to MoE: the
classic GShard einsum dispatch materialises a (tokens × experts × capacity)
tensor — at this repo's shapes that is >100 GB per device, a non-starter.
Instead each model-axis shard owns ``E/tp`` experts and dispatches locally:

  1. route on the (model-replicated) token block: top-k over E experts,
  2. sort token-expert assignments, rank within expert (capacity C drop),
  3. gather into (E_local, C, d), two grouped einsums, weighted scatter-add,
  4. ONE psum over the model axis combines expert partials + the shared
     expert's tensor-parallel partial — the same single-collective structure
     as the paper's fused MPI_Allreduce of scalar pairs, at tensor scale.

The router compute (step 1-2) is independent of the expert weights and sits
*before* the psum in the dependence graph — the overlap property CG-NB gives
its reductions (DESIGN.md §4).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.mlp import _act


def init_moe_params(key, cfg: ArchConfig, dtype) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * d ** -0.5
                   ).astype(jnp.float32),
        "w_in": (jax.random.normal(ks[1], (E, d, 2 * f), jnp.float32) * d ** -0.5
                 ).astype(dtype),
        "w_out": (jax.random.normal(ks[2], (E, f, d), jnp.float32) * f ** -0.5
                  ).astype(dtype),
    }
    if cfg.shared_expert:
        p["shared_in"] = (jax.random.normal(ks[3], (d, 2 * f), jnp.float32)
                          * d ** -0.5).astype(dtype)
        p["shared_out"] = (jax.random.normal(ks[4], (f, d), jnp.float32)
                           * f ** -0.5).astype(dtype)
    return p


def capacity(T: int, cfg: ArchConfig) -> int:
    c = int(math.ceil(T * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # >=8, rounded up to a multiple of 8


def moe_forward(p, cfg: ArchConfig, x, mesh: Mesh, dp_axes: tuple[str, ...],
                tp_axis: str):
    """x: (B, S, d) global (batch sharded over dp, replicated over tp)."""
    E, k_top, d, f = cfg.n_experts, cfg.top_k, cfg.d_model, cfg.d_ff
    tp = mesh.shape[tp_axis]
    assert E % tp == 0, (E, tp)
    E_local = E // tp

    #: at/below this many token-expert assignments the gather path wins:
    #: dense dispatch reads EVERY resident expert's weights regardless of
    #: routing (measured: llama4 decode_32k reads ~2 GB/layer/device for 8
    #: tokens), while gathering the routed experts' weights costs
    #: assignments × one expert slice.
    GATHER_MAX_ASSIGNMENTS = 64

    def gather_fn(router_w, w_in, w_out, shared, x_loc):
        B, S, _ = x_loc.shape
        T = B * S
        xt = x_loc.reshape(T, d)
        logits = (xt.astype(jnp.float32) @ router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = lax.top_k(probs, k_top)                    # (T, k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        E_local = w_in.shape[0]
        shard = lax.axis_index(tp_axis)
        flat_e = idx.reshape(-1)
        flat_t = jnp.arange(T * k_top) // k_top
        flat_w = w.reshape(-1)
        le = flat_e - shard * E_local
        mine = (le >= 0) & (le < E_local)

        def body(y, inp):
            t, e_loc, ok, wgt = inp
            wi = w_in[jnp.clip(e_loc, 0, E_local - 1)]       # (d, 2f)
            h = xt[t] @ wi
            h = _act(cfg.act)(h[:f]) * h[f:]
            o = (h @ w_out[jnp.clip(e_loc, 0, E_local - 1)]) * wgt.astype(
                xt.dtype)
            return y.at[t].add(jnp.where(ok, o, 0)), None

        y0 = jnp.zeros((T, d), xt.dtype)
        # match the scan carry's varying-manual-axes to the body output
        y0 = lax.pcast(y0, tuple(dp_axes) + (tp_axis,), to="varying")
        out, _ = lax.scan(body, y0, (flat_t, le, mine, flat_w))
        if cfg.shared_expert:
            sh_in, sh_out = shared
            f_loc = sh_out.shape[0]
            hs = xt @ sh_in
            out = out + (_act(cfg.act)(hs[:, :f_loc]) * hs[:, f_loc:]) @ sh_out
        out = lax.psum(out, tp_axis)
        me = lax.pmean(jnp.mean(probs, axis=0), dp_axes)
        ce = lax.pmean(
            jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * k_top),
            dp_axes)
        aux = E * jnp.sum(me * ce)
        return out.reshape(B, S, d).astype(x_loc.dtype), aux

    dp_total = 1
    for a in dp_axes:
        dp_total *= mesh.shape[a]

    def local_fn(router_w, w_in, w_out, shared, x_loc):
        B, S, _ = x_loc.shape
        T = B * S
        # gather wins ONLY when a shard sees fewer assignments than it owns
        # experts (measured: at decode_32k's B_loc·k ≈ E/tp the two paths
        # read the same weight bytes — EXPERIMENTS.md §Perf-3c, refuted).
        # Decided on the GLOBAL count: the paths differ in drop semantics
        # (gather never drops), so dp layouts must not flip the choice.
        if T * dp_total * k_top <= min(GATHER_MAX_ASSIGNMENTS, E // tp - 1):
            return gather_fn(router_w, w_in, w_out, shared, x_loc)
        # capacity and drop decisions must be dp-invariant: C from the GLOBAL
        # token count, ranks offset by earlier dp shards' per-expert loads —
        # otherwise distributed and single-device runs drop DIFFERENT
        # token-expert assignments and the losses diverge (the old per-shard
        # capacity(T_local) was off by the dp rounding AND re-ranked each
        # shard's tokens from zero).
        C = capacity(T * dp_total, cfg)
        xt = x_loc.reshape(T, d)
        # --- routing (replicated over tp; independent of expert weights) ----
        logits = (xt.astype(jnp.float32) @ router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = lax.top_k(probs, k_top)                    # (T, k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        flat_e = idx.reshape(-1)                            # (T*k,)
        order = jnp.argsort(flat_e)
        se = flat_e[order]
        st = (jnp.arange(T * k_top) // k_top)[order]
        sw = w.reshape(-1)[order]
        starts = jnp.searchsorted(se, jnp.arange(E))
        rank = jnp.arange(T * k_top) - starts[se]           # local stable rank
        rank_g = rank
        if dp_total > 1:
            # global rank = local rank + assignments to the same expert on
            # dp shards owning EARLIER tokens (batch is laid out row-major
            # over dp_axes, matching all_gather's tuple order)
            counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
            all_counts = lax.all_gather(counts, dp_axes, axis=0)  # (dp, E)
            lin = jnp.int32(0)
            for a in dp_axes:
                lin = lin * mesh.shape[a] + lax.axis_index(a)
            before = jnp.arange(dp_total, dtype=jnp.int32) < lin
            offset = jnp.sum(jnp.where(before[:, None], all_counts, 0), axis=0)
            rank_g = rank + offset[se]
        # --- my experts ------------------------------------------------------
        shard = lax.axis_index(tp_axis)
        le = se - shard * E_local
        # drop on the GLOBAL rank (same set as a single-device run); slots
        # index by the LOCAL rank, so the dispatch buffers stay sized by
        # what this shard can actually fill (rank < min(C, T·k) always,
        # since rank <= rank_g < C and a shard has T·k assignments) — NOT
        # by the dp-independent global capacity
        Cs = min(C, T * k_top)
        valid = (le >= 0) & (le < E_local) & (rank_g < C)
        slot = jnp.where(valid, le * Cs + rank, E_local * Cs)  # OOB -> dropped
        table = jnp.full((E_local * Cs,), T, jnp.int32).at[slot].set(
            st.astype(jnp.int32), mode="drop")
        wtab = jnp.zeros((E_local * Cs,), jnp.float32).at[slot].set(
            sw, mode="drop")
        x_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])
        xg = x_pad[table].reshape(E_local, Cs, d)
        h = jnp.einsum("ecd,edf->ecf", xg, w_in)
        gate, up = h[..., :f], h[..., f:]
        h = _act(cfg.act)(gate) * up
        y = jnp.einsum("ecf,efd->ecd", h, w_out)
        y = y * wtab.reshape(E_local, Cs, 1).astype(y.dtype)
        y_flat = jnp.zeros((T + 1, d), y.dtype).at[table].add(
            y.reshape(E_local * Cs, d))
        out = y_flat[:T]
        # --- shared expert: plain tensor-parallel MLP partial ----------------
        if cfg.shared_expert:
            sh_in, sh_out = shared
            f_loc = sh_out.shape[0]
            hs = xt @ sh_in
            out = out + (_act(cfg.act)(hs[:, :f_loc]) * hs[:, f_loc:]) @ sh_out
        out = lax.psum(out, tp_axis)                         # ONE collective
        # --- load-balance aux (Switch-style), replicated ---------------------
        # pmean the per-expert vectors BEFORE the bilinear product: the aux
        # is E·Σ_e me_e·ce_e over the GLOBAL batch; averaging per-shard
        # products instead is a different (dp-dependent) number
        me = lax.pmean(jnp.mean(probs, axis=0), dp_axes)     # (E,)
        ce = lax.pmean(
            jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * k_top),
            dp_axes)
        aux = E * jnp.sum(me * ce)
        return out.reshape(B, S, d).astype(x_loc.dtype), aux

    dp = P(dp_axes if len(dp_axes) > 1 else dp_axes[0])
    x_spec = P(*dp, None, None) if len(dp_axes) else P(None, None, None)
    shared_specs = (P(None, None), P(None, None))
    if cfg.shared_expert:
        shared_specs = (P(None, tp_axis), P(tp_axis, None))
        shared = (p["shared_in"], p["shared_out"])
    else:
        shared = (jnp.zeros((1, 2), x.dtype), jnp.zeros((1, 1), x.dtype))
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(None, None),                    # router replicated
            P(tp_axis, None, None),           # experts sharded over tp
            P(tp_axis, None, None),
            shared_specs,
            x_spec,
        ),
        out_specs=(x_spec, P()),
    )
    return fn(p["router"], p["w_in"], p["w_out"], shared, x)

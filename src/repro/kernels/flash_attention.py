"""Pallas TPU kernel: causal flash attention (online softmax, VMEM-blocked).

The on-TPU endpoint of ``models/attention._chunked_sdpa``: same blocking
(q-chunks × kv-chunks, running max/denominator in f32), but as an explicit
``pl.pallas_call`` with VMEM BlockSpecs — one (bq × hd) accumulator and one
(bq × bkv) score tile resident per grid step, HBM traffic 1× q + nq-fold k/v
streaming, no (S, S) materialisation.

Grid: (B·H, nq, nkv), kv innermost — TPU executes grid steps sequentially per
core, so the f32 scratch accumulators carry across the kv dimension and are
re-initialised at kv block 0 (the same revisiting-output pattern as the
solver kernels' fused dots).  Causal blocks above the diagonal are predicated
off with ``pl.when``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocks import out_struct, pallas_call

NEG_INF = -2.3819763e38


def _kernel(bq: int, bkv: int, hd: int, scale: float, window: int):
    def body(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        i = pl.program_id(1)          # q block
        j = pl.program_id(2)          # kv block
        nk = pl.num_programs(2)

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full((bq,), -jnp.inf, jnp.float32)
            l_scr[...] = jnp.zeros((bq,), jnp.float32)
            acc_scr[...] = jnp.zeros((bq, hd), jnp.float32)

        @pl.when(j * bkv <= i * bq + bq - 1)   # causal: block reachable
        def _compute():
            q = q_ref[0]              # (bq, hd)
            k = k_ref[0]              # (bkv, hd)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            q_idx = i * bq + jnp.arange(bq)
            k_idx = j * bkv + jnp.arange(bkv)
            mask = k_idx[None, :] <= q_idx[:, None]
            if window:
                mask &= k_idx[None, :] > (q_idx[:, None] - window)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
            acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
                p.astype(v_ref.dtype), v_ref[0]).astype(jnp.float32)
            m_scr[...] = m_new

        @pl.when(j == nk - 1)
        def _finish():
            o_ref[0] = (acc_scr[...] /
                        jnp.maximum(l_scr[...], 1e-30)[:, None]
                        ).astype(o_ref.dtype)

    return body


@functools.partial(jax.jit,
                   static_argnames=("bq", "bkv", "window", "interpret"))
def flash_attention(
    q: jax.Array,           # (B, S, H, hd)
    k: jax.Array,           # (B, S, H, hd)  (KV already repeated to H)
    v: jax.Array,
    *,
    bq: int = 256,
    bkv: int = 256,
    window: int = 0,
    interpret: bool,
) -> jax.Array:
    B, S, H, hd = q.shape
    while S % bq:
        bq -= 1
    while S % bkv:
        bkv -= 1
    scale = hd ** -0.5
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    out = pallas_call(
        _kernel(bq, bkv, hd, scale, window),
        grid=(B * H, S // bq, S // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=out_struct((B * H, S, hd), q.dtype, q),
        scratch_shapes=[
            # (bq,) running max, (bq,) denominator, (bq, hd) accumulator —
            # persist across the sequential kv grid dim (VMEM on TPU)
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)

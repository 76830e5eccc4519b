"""Block helpers shared by every Pallas kernel here.

* ``out_struct`` — an output's shape and dtype, varying over the same mesh
  axes as the operand it is computed from (a kernel inside ``shard_map``
  must say so);
* ``scalar_spec`` / ``accumulate`` — a (1, k) array of coefficients or dot
  partials, whole, in SMEM: the TPU stores scalars there, not in VMEM.  An
  accumulator is zeroed at grid step 0 and revisited by every later step
  (TPU grid steps run in order, so the sum is well-defined);
* ``acc_dtype`` — the accumulation dtype of a reduction over ``dtype``;
* ``pallas_call`` — ``pl.pallas_call`` that traces a compiled (not
  interpreted) kernel with x64 off.  With x64 on, the Python ints of index
  maps and loop bounds become int64, which the TPU's kernel compiler does
  not lower; interpret mode keeps the caller's setting, since it runs the
  float64 kernels on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def out_struct(shape, dtype, like: jax.Array) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def scalar_spec() -> pl.BlockSpec:
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def accumulate(acc, partials) -> None:
    """Add one grid step's ``partials`` into the (1, k) SMEM ``acc``."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        for k in range(len(partials)):
            acc[0, k] = jnp.zeros((), acc.dtype)

    for k, v in enumerate(partials):
        acc[0, k] += v


def acc_dtype(dtype):
    return jnp.float32 if dtype == jnp.bfloat16 else dtype


def pallas_call(kernel, *, interpret: bool, **kwargs):
    call = pl.pallas_call(kernel, interpret=interpret, **kwargs)
    if interpret:
        return call

    def compiled(*args):
        with jax.enable_x64(False):
            return call(*args)

    return compiled

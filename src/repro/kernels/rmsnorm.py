"""Fused RMSNorm for TPU (Pallas).

One pass over a [rows, d] tile in VMEM: fp32 mean-of-squares reduction on
the VPU, rsqrt, scale — avoiding the three separate HBM round-trips XLA
sometimes emits for norm(x) when the producer/consumer don't fuse.  Rows
tile by ``block_rows``; the feature dim rides whole (d <= ~16k fits VMEM
at fp32 for 8+ rows)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas.tpu import CompilerParams


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool | None = None) -> jax.Array:
    """x: [rows, d]; scale: [d]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    rows, d = x.shape
    br = min(block_rows, rows)
    if rows % br:
        raise ValueError(f"rows {rows} not divisible by block {br}")
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, scale[None, :])

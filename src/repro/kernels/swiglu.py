"""Fused SwiGLU feed-forward gate for TPU (Pallas).

Computes silu(x @ w_gate) * (x @ w_up) with one kernel: both matmuls tile
the same [bm, bk] x-block through the MXU (k-axis innermost/sequential,
fp32 accumulators in VMEM scratch), and the silu-and-multiply epilogue runs
on the VPU when the k-loop finishes — so the gate tensor never round-trips
to HBM.  Blocks default to 128x128x512, MXU-aligned."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.experimental.pallas.tpu import CompilerParams


def _swiglu_kernel(x_ref, wg_ref, wu_ref, o_ref, accg_ref, accu_ref, *,
                   n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...]
    accg_ref[...] += jax.lax.dot(x, wg_ref[...],
                                 preferred_element_type=jnp.float32)
    accu_ref[...] += jax.lax.dot(x, wu_ref[...],
                                 preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        g = accg_ref[...]
        o_ref[...] = (g * jax.lax.logistic(g) * accu_ref[...]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret"))
def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           block_m: int = 128, block_n: int = 128, block_k: int = 512,
           interpret: bool | None = None) -> jax.Array:
    """x: [M, K]; w_gate/w_up: [K, N] -> [M, N]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    M, K = x.shape
    N = w_gate.shape[1]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"shape ({M},{K},{N}) not divisible by blocks")
    n_k = K // bk
    return pl.pallas_call(
        functools.partial(_swiglu_kernel, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_gate, w_up)

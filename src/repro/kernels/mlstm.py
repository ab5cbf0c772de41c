"""Fused mLSTM parallel-form kernel for TPU (Pallas).

The xLSTM mLSTM parallel form is attention-with-additive-decay:

    D[t,s]   = F_t - F_s + i_s           (s <= t; F = cumsum log forget)
    S[t,s]   = (q_t . k_s) * exp(D - m)  (m = running row max, stabiliser)
    y_t      = sum_s S[t,s] v_s / max(|sum_s S[t,s]|, exp(-m))

This kernel is the §Perf-identified fix for xlstm-350m's memory floor: the
jnp path streams the [chunk, S] fp32 decay/score slabs through HBM
(~3e14 B/step at train_4k); here they live in VMEM scratch only, exactly
like flash attention's probability block.  Same online-rescaling scheme as
flash, with a *signed* running denominator (mLSTM normalises by
|sum of scores|, not a softmax partition function).

Layout: q/k/v [BH, S, hd]; F/i [BH, S].  Grid (BH, S/bq, S/bk), kv-axis
innermost sequential with VMEM carries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.experimental.pallas.tpu import CompilerParams


def _mlstm_kernel(q_ref, k_ref, v_ref, f_ref, fk_ref, i_ref, o_ref,
                  acc_ref, m_ref, den_ref, *, bq: int, bk: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        den_ref[...] = jnp.zeros_like(den_ref)

    q = q_ref[0].astype(jnp.float32)                   # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                   # [bk, hd]
    v = v_ref[0].astype(jnp.float32)
    Fq = f_ref[0].astype(jnp.float32)                  # [bq]
    Fk = fk_ref[0].astype(jnp.float32)                 # [bk]
    ik = i_ref[0].astype(jnp.float32)                  # [bk]

    # decay matrix D[t,s] = F_t - F_s + i_s, causal-masked
    D = Fq[:, None] - Fk[None, :] + ik[None, :]        # [bq, bk]
    t_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    s_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = t_idx >= s_idx
    D = jnp.where(mask, D, -1e30)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, D.max(axis=-1, keepdims=True))
    w = jnp.exp(D - m_new) * mask
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * w
    alpha = jnp.exp(m_prev - m_new)
    den_ref[...] = den_ref[...] * alpha + scores.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(scores, v)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        norm = jnp.maximum(jnp.abs(den_ref[...]), jnp.exp(-m_ref[...]))
        o_ref[0] = (acc_ref[...] / norm).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def mlstm_parallel(q: jax.Array, k: jax.Array, v: jax.Array,
                   F: jax.Array, i_pre: jax.Array, *,
                   block_q: int = 128, block_k: int = 128,
                   interpret: bool | None = None) -> jax.Array:
    """q/k/v: [BH, S, hd] (k pre-scaled by 1/sqrt(hd));
    F: [BH, S] cumulative log-forget; i_pre: [BH, S] input-gate
    pre-activations.  Returns [BH, S, hd]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    BH, S, hd = q.shape
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S % bq or S % bk:
        raise ValueError(f"S={S} not divisible by blocks ({bq},{bk})")
    n_kv = S // bk
    kernel = functools.partial(_mlstm_kernel, bq=bq, bk=bk, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=(BH, S // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, bk), lambda b, i, j: (b, j)),
            pl.BlockSpec((1, bk), lambda b, i, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, F, F, i_pre)   # F enters twice: q-row block and k-row block

"""Flash attention for TPU (Pallas): blockwise online-softmax.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * tiling is chosen for VMEM and the 128x128 MXU — q/k blocks are
    multiples of 128 on the sequence axes and the full head dim rides along
    (head_dim <= 256 fits VMEM comfortably: bq*hd + 2*bk*hd + bq*bk floats);
  * the kv axis is the innermost *sequential* grid dimension
    ("arbitrary"), carrying the running max/denominator/accumulator in VMEM
    scratch across kv steps — the TPU grid is executed in order, which
    replaces the CUDA shared-memory + warp-shuffle reduction;
  * causal/sliding-window masking and the gemma2 logit softcap are fused
    into the block, so masked kv blocks cost one predicated VPU pass
    instead of a second kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.experimental.pallas.tpu import CompilerParams


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, n_kv: int, causal: bool, window: int,
                  softcap: float, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                    # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                    # [bk, hd]
    v = v_ref[0].astype(jnp.float32)
    hd = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) / jnp.sqrt(
        jnp.float32(hd))                                # [bq, bk]
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_idx < kv_len                               # kv padding
    if causal:
        rel = q_idx - k_idx
        mask &= rel >= 0
        if window:
            mask &= rel < window

    s = jnp.where(mask, s, -1e30)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * mask                       # masked rows stay 0
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "kv_len",
    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, softcap: float = 0.0,
                    block_q: int = 128, block_k: int = 128,
                    kv_len: int = 0, interpret: bool | None = None) -> jax.Array:
    """q/k/v: [B, H, S, hd] with equal head counts.  Returns [B, H, Sq, hd].

    Sequence lengths must be multiples of the block sizes (ops.py pads);
    ``kv_len`` marks the number of *real* kv positions (0 = all)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, H, Sq, hd = q.shape
    Skv = k.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    if Sq % bq or Skv % bk:
        raise ValueError(f"seq lens ({Sq},{Skv}) not divisible by blocks ({bq},{bk})")
    n_kv = Skv // bk
    qr = q.reshape(B * H, Sq, hd)
    kr = k.reshape(B * H, Skv, hd)
    vr = v.reshape(B * H, Skv, hd)
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=n_kv, causal=causal,
        window=window, softcap=softcap, kv_len=kv_len or Skv)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, hd)

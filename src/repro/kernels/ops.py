"""Jit'd public wrappers around the Pallas kernels.

These handle layout adaptation (GQA head repetition, sequence padding to
block multiples, [B,S,H,hd] <-> [B,H,S,hd]) so model code can call them
with natural shapes.  On CPU the kernels execute in interpret mode; on TPU
they compile to Mosaic."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as _jfa

from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import swiglu as _sg


# (query block, key block) caps of the training kernel's forward, dk/dv
# and dq kernels: the fastest pairs of a sweep over {256, 512, 1024}^2 on
# a TPU v5e at 2 x 4,096 positions, 14 heads of 64
# (benchmarks/flash_block_sweep.py; PERF.md).  ``causal_blocks`` cuts
# each cap to a block that divides the sequence.
FWD_BLOCKS = (1024, 1024)
DKV_BLOCKS = (1024, 1024)
DQ_BLOCKS = (1024, 512)


def _pad_to(x: jax.Array, axis: int, mult: int) -> tuple[jax.Array, int]:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    """Model-layout entry point: q [B,S,H,hd], k/v [B,S,K,hd] (GQA ok).

    Repeats kv heads to match q heads, pads S to block multiples (padded
    kv columns carry position > any real q so the causal mask kills them),
    and returns [B,S,H,hd]."""
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    if H != K:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = min(block_q, 1 << (Sq - 1).bit_length())       # pow2 cap
    bk = min(block_k, 1 << (Skv - 1).bit_length())
    qt, pad_q = _pad_to(qt, 2, bq)
    kt, _ = _pad_to(kt, 2, bk)
    vt, _ = _pad_to(vt, 2, bk)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              softcap=softcap, block_q=bq, block_k=bk,
                              kv_len=Skv)
    if pad_q:
        out = out[:, :, :Sq]
    return out.transpose(0, 2, 1, 3)


def _divisor_block(seq: int, cap: int) -> int:
    """``seq`` where it is no longer than ``cap``, else the power-of-two
    ``cap`` halved until it divides ``seq`` (a multiple of 128)."""
    b = min(cap, seq)
    while seq % b:
        b //= 2
    return b


def causal_blocks(seq: int, fwd: tuple = FWD_BLOCKS,
                  dkv: tuple = DKV_BLOCKS,
                  dq: tuple = DQ_BLOCKS) -> _jfa.BlockSizes:
    """Block sizes of the training kernel at sequence length ``seq``: each
    kernel's (query, key) caps, each cut to a block that divides ``seq``
    (``_divisor_block``)."""
    (fq, fk), (vq, vk), (qq, qk) = (
        [_divisor_block(seq, b) for b in pair] for pair in (fwd, dkv, dq))
    return _jfa.BlockSizes(
        block_q=fq, block_k_major=fk, block_k=fk, block_b=1,
        block_q_major_dkv=vq, block_k_major_dkv=vk, block_k_dkv=vk,
        block_q_dkv=vq, block_k_major_dq=qk, block_k_dq=qk, block_q_dq=qq)


def causal_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           blocks: _jfa.BlockSizes | None = None
                           ) -> jax.Array:
    """Causal attention on JAX's bundled TPU flash kernel, which has a
    Pallas backward (dq and dk/dv kernels) and skips key blocks wholly
    above the diagonal.  Model layout: q [B,S,H,hd], k/v [B,S,K,hd] (GQA:
    the kv heads are repeated, so the repeat's VJP sums dk/dv over each
    group); returns [B,S,H,hd].  Products take the inputs' dtype with f32
    accumulation; the running max and denominator are f32.  ``S`` must be
    a multiple of 128 and positions are ``0..S-1``."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    if H != K:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    out = _jfa.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, sm_scale=1.0 / math.sqrt(hd),
        block_sizes=blocks or causal_blocks(S))
    return out.transpose(0, 2, 1, 3)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x: [..., d] any leading shape."""
    shape = x.shape
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, shape[-1])
    br = 256
    while rows % br and br > 1:
        br //= 2
    out = _rn.rmsnorm(x2, scale, eps=eps, block_rows=br)
    return out.reshape(shape)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array) -> jax.Array:
    """x: [..., K]; w: [K, N]."""
    shape = x.shape
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, shape[-1])
    bm = 128
    while rows % bm and bm > 1:
        bm //= 2
    bn = 128
    while w_gate.shape[1] % bn and bn > 1:
        bn //= 2
    bk = 512
    while shape[-1] % bk and bk > 1:
        bk //= 2
    out = _sg.swiglu(x2, w_gate, w_up, block_m=bm, block_n=bn, block_k=bk)
    return out.reshape(shape[:-1] + (w_gate.shape[1],))

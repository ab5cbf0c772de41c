"""Composable transformer building blocks (pure-function + pytree params).

Everything is shape-polymorphic and jit/scan/shard_map friendly:

  * ``rms_norm``          -- RMSNorm (ref path; Pallas kernel in kernels/)
  * ``apply_rope``        -- rotary embeddings, "full" (llama) or "half"
                             (chatglm 2d-rope: only the first half of the
                             head dim rotates)
  * ``attention``         -- GQA attention with optional sliding window,
                             logit softcap (gemma2), KV cache with absolute
                             slot positions (supports rolling caches), and
                             cross-attention (whisper)
  * ``mlp``               -- swiglu / geglu / gelu feed-forward
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# activation-sharding hints
# ---------------------------------------------------------------------------


def shard_hint(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint that degrades gracefully: each entry of
    ``axes`` is None | axis-name | tuple-of-names; an axis is applied only
    if it is an ``Auto`` axis of the ambient (abstract) mesh and divides
    the dim (an ``Explicit`` axis carries its sharding in the array type,
    where a constraint would be an assert).  On an un-meshed trace (CPU
    smoke tests) this is the identity, so models stay mesh-agnostic."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    auto = {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t == jax.sharding.AxisType.Auto}
    spec = []
    for dim, ax in zip(x.shape, axes):
        if ax is None:
            spec.append(None)
            continue
        cand = tuple(a for a in ((ax,) if isinstance(ax, str) else ax)
                     if a in auto)
        size = 1
        for a in cand:
            size *= mesh.shape[a]
        if cand and size > 1 and dim % size == 0:
            spec.append(cand if len(cand) > 1 else cand[0])
        else:
            spec.append(None)
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


BATCH_AXES = ("pod", "data")


@jax.custom_vjp
def bf16_grad_barrier(x: jax.Array) -> jax.Array:
    """Identity forward; casts the cotangent to bf16 on the way back.

    Placed at block boundaries it pins the backward residual stream (and
    therefore the gradient all-reduces XLA inserts around model-sharded
    matmul transposes) to bf16 instead of the fp32 that loss-side upcasts
    otherwise propagate — halving backward collective and HBM bytes
    (§Perf hillclimb, llama3-405b x train_4k)."""
    return x


def _bf16_barrier_fwd(x):
    return x, None


def _bf16_barrier_bwd(_, g):
    return (g.astype(jnp.bfloat16),)


bf16_grad_barrier.defvjp(_bf16_barrier_fwd, _bf16_barrier_bwd)


# ---------------------------------------------------------------------------
# norms & embeddings
# ---------------------------------------------------------------------------


def init_rms_norm(d: int, dtype) -> jax.Array:
    return jnp.ones((d,), dtype=dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float,
             cast_early: bool = False) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    if cast_early:
        # normalise in fp32 but cross op boundaries in compute dtype: the
        # scale-mul (and any downstream collective) sees bf16, halving the
        # bytes XLA moves when it hoists converts across gathers (§Perf)
        y = (x32 * jax.lax.rsqrt(var + eps)).astype(dt)
        return y * scale.astype(dt)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def init_embedding(rng, vocab: int, d: int, dtype) -> jax.Array:
    return jax.random.normal(rng, (vocab, d), dtype=jnp.float32).astype(dtype) * 0.02


def sinusoidal_positions(seq: int, d: int, dtype=jnp.float32) -> jax.Array:
    """Whisper-style sinusoidal position embeddings [seq, d]."""
    pos = jnp.arange(seq)[:, None].astype(jnp.float32)
    dim = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    inv = jnp.exp(-jnp.log(10000.0) * dim / max(d // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def _rope_rotate(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate all of the last dim of x [..., S, H, D] at ``positions`` [..., S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs      # [..., S, half]
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mode: str) -> jax.Array:
    """x: [B, S, H, D]; positions: [B, S] absolute token positions."""
    if mode == "none":
        return x
    if mode == "full":
        return _rope_rotate(x, positions, theta)
    if mode == "half":                           # chatglm 2d rope
        d = x.shape[-1]
        rotated = _rope_rotate(x[..., : d // 2], positions, theta)
        return jnp.concatenate([rotated, x[..., d // 2:]], axis=-1)
    raise ValueError(f"unknown rope mode {mode}")


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KVCache:
    """Decode-time cache with absolute slot positions (rolling-capable).

    ``k``/``v``: [B, Smax, K, hd]; ``pos``: [B, Smax] absolute position held
    in each slot, -1 when the slot is empty.  A rolling cache (long-context
    sliding window) simply writes at slot ``position % Smax``.

    int8 mode (beyond-paper, §Perf decode-memory lever): k/v stored int8
    with per-(batch, slot, head) symmetric scales — halves cache residency
    vs bf16 at <1% relative dequant error per entry."""
    k: jax.Array
    v: jax.Array
    pos: jax.Array
    k_scale: jax.Array | None = None     # [B, Smax, K] fp32, int8 mode only
    v_scale: jax.Array | None = None

    def tree_flatten(self):
        return (self.k, self.v, self.pos, self.k_scale, self.v_scale), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


jax.tree_util.register_pytree_node(
    KVCache, KVCache.tree_flatten, KVCache.tree_unflatten)


def init_kv_cache(batch: int, max_slots: int, n_kv: int, head_dim: int,
                  dtype) -> KVCache:
    dt = jnp.dtype(dtype)
    quant = dt == jnp.int8
    shape = (batch, max_slots, n_kv, head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype=dt),
        v=jnp.zeros(shape, dtype=dt),
        pos=jnp.full((batch, max_slots), -1, dtype=jnp.int32),
        k_scale=jnp.zeros((batch, max_slots, n_kv), jnp.float32)
        if quant else None,
        v_scale=jnp.zeros((batch, max_slots, n_kv), jnp.float32)
        if quant else None,
    )


def _quantize_kv(x):
    """x: [B, S, K, hd] -> (int8 values, per-[B,S,K] scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    safe = jnp.maximum(scale, 1e-9)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / safe[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def init_attn(rng, cfg: ModelConfig, dtype, *, n_heads=None, n_kv=None):
    h = n_heads or cfg.n_heads
    k = n_kv or cfg.n_kv_heads
    d, hd = cfg.d_model, cfg.head_dim
    r = jax.random.split(rng, 4)
    s = 1.0 / jnp.sqrt(d)
    return {
        "wq": (jax.random.normal(r[0], (d, h * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(r[1], (d, k * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(r[2], (d, k * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(r[3], (h * hd, d)) * s).astype(dtype),
    }


def _sdpa(q, k, v, q_pos, k_pos, *, causal: bool, window, softcap: float,
          compute_dtype) -> jax.Array:
    """Reference scaled-dot-product attention with GQA + masks.

    q: [B,Sq,H,hd]; k/v: [B,Skv,Kh,hd]; q_pos: [B,Sq]; k_pos: [B,Skv]
    (absolute positions; k_pos = -1 marks invalid slots).
    ``window`` may be a python int or a traced scalar (0 = unlimited).
    """
    B, Sq, H, hd = q.shape
    Kh = k.shape[2]
    G = H // Kh
    qg = q.reshape(B, Sq, Kh, G, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)

    valid = (k_pos >= 0)[:, None, :]                           # [B,1,Skv]
    if causal:
        rel = q_pos[:, :, None] - k_pos[:, None, :]            # [B,Sq,Skv]
        valid = valid & (rel >= 0)
        window = jnp.asarray(window)
        valid = valid & ((window <= 0) | (rel < window))
    big_neg = jnp.asarray(-1e30, jnp.float32)
    logits = jnp.where(valid[:, None, None, :, :], logits, big_neg)
    p = jax.nn.softmax(logits, axis=-1).astype(compute_dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
    return out.reshape(B, Sq, H * hd)


def _model_axis_size() -> int:
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:                                   # pragma: no cover
        return 1
    if mesh is None or "model" not in (mesh.axis_names or ()):
        return 1
    return int(mesh.shape["model"])


def _sdpa_q_chunked(q, k, v, q_pos, k_pos, *, causal, window, softcap,
                    compute_dtype, q_chunk, cp=False):
    """Flash-style memory shape without Pallas: scan over query chunks so the
    [Sq, Skv] score matrix never materialises whole (the per-chunk
    [q_chunk, Skv] slab is transient and rematerialised in the backward).
    Numerically identical to _sdpa — used for long sequences in the pjit
    path; the Pallas kernel (kernels/flash_attention.py) is the TPU
    fast path."""
    B, Sq, H, hd = q.shape
    nc = Sq // q_chunk
    qs = jnp.moveaxis(q.reshape(B, nc, q_chunk, H, hd), 1, 0)
    qp = jnp.moveaxis(q_pos.reshape(B, nc, q_chunk), 1, 0)

    def body(_, inp):
        qc, qpc = inp
        if cp:
            # context-parallel fallback (heads don't tile the model axis):
            # split this chunk's query rows over "model"; k/v replicated.
            qc = shard_hint(qc, BATCH_AXES, "model", None, None)
        out = _sdpa(qc, k, v, qpc, k_pos, causal=causal, window=window,
                    softcap=softcap, compute_dtype=compute_dtype)
        return None, out

    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qs, qp))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H * hd)


def _sdpa_auto(q, k, v, q_pos, k_pos, *, causal, window, softcap,
               compute_dtype, q_chunk, n_heads=0):
    Sq = q.shape[1]
    # heads that don't tile the model axis can't head-shard the einsum;
    # shard the query sequence instead (each q row attends the full kv)
    import os
    ms = _model_axis_size()
    cp = bool(ms > 1 and n_heads and n_heads % ms != 0
              and not os.environ.get("REPRO_NAIVE_SHARDING"))
    if cp:
        k = shard_hint(k, BATCH_AXES, None, None, None)
        v = shard_hint(v, BATCH_AXES, None, None, None)
    if q_chunk and Sq > q_chunk and Sq % q_chunk == 0:
        return _sdpa_q_chunked(q, k, v, q_pos, k_pos, causal=causal,
                               window=window, softcap=softcap,
                               compute_dtype=compute_dtype, q_chunk=q_chunk,
                               cp=cp)
    if cp:
        q = shard_hint(q, BATCH_AXES, "model", None, None)
    out = _sdpa(q, k, v, q_pos, k_pos, causal=causal, window=window,
                softcap=softcap, compute_dtype=compute_dtype)
    return out if not cp else shard_hint(
        out.reshape(out.shape), BATCH_AXES, None, None)


# Which path each attention call took, counted when the call is lowered
# (``jax.jit(...).lower``), where the platform is known: "fused" is the
# bundled Pallas kernel, "jnp" everything else.  Tests read it.
ATTENTION_PATHS = {"fused": 0, "jnp": 0}

_path_p = Primitive("attention_path")       # identity that counts a path


def _count_path(x, *, path):
    ATTENTION_PATHS[path] += 1
    return x


_path_p.def_impl(_count_path)
_path_p.def_abstract_eval(lambda x, *, path: x)
mlir.register_lowering(_path_p, lambda ctx, x, *, path: [
    _count_path(x, path=path)])
ad.primitive_jvps[_path_p] = lambda primals, tangents, *, path: (
    _path_p.bind(*primals, path=path), tangents[0])
batching.primitive_batchers[_path_p] = lambda args, dims, *, path: (
    _path_p.bind(*args, path=path), dims[0])


def _path(out: jax.Array, path: str) -> jax.Array:
    return _path_p.bind(out, path=path)


def _fused_eligible(cfg: ModelConfig, S: int, window, causal: bool) -> bool:
    """The training path's causal self-attention goes to the fused kernel
    where the chunked jnp path would engage (``S > q_chunk``, ``S`` a
    multiple of 128), with no window, softcap or model-axis sharding."""
    return (causal and isinstance(window, int) and window == 0
            and not cfg.attn_softcap and bool(cfg.q_chunk)
            and S > cfg.q_chunk and S % 128 == 0 and _model_axis_size() == 1)


def attention(cfg: ModelConfig, p, x, q_pos, *, window=0, cache: KVCache | None = None,
              enc_out: jax.Array | None = None, rope: bool = True,
              causal: bool = True) -> tuple:
    """Self- or cross-attention.

    Returns (output, new_cache).  ``cache`` given => decode: x holds the new
    token(s); K/V are written into the cache at slot ``q_pos % Smax``.
    ``enc_out`` given => cross-attention (no mask, no rope, no cache).
    """
    cd = jnp.dtype(cfg.compute_dtype)
    B, S, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(cd)).reshape(B, S, h, hd)
    kv_src = enc_out if enc_out is not None else x
    Skv = kv_src.shape[1]
    k = (kv_src @ p["wk"].astype(cd)).reshape(B, Skv, kh, hd)
    v = (kv_src @ p["wv"].astype(cd)).reshape(B, Skv, kh, hd)

    if enc_out is not None:
        k_pos = jnp.zeros((B, Skv), jnp.int32)                 # all valid
        out = _path(_sdpa_auto(
            q, k, v, q_pos, k_pos, causal=False, window=0,
            softcap=cfg.attn_softcap, compute_dtype=cd, q_chunk=cfg.q_chunk,
            n_heads=cfg.n_heads), "jnp")
        return out @ p["wo"].astype(cd), None

    if rope:
        q = apply_rope(q, q_pos, cfg.rope_theta, cfg.rope)
        k = apply_rope(k, q_pos, cfg.rope_theta, cfg.rope)

    if cache is None:
        if cfg.use_flash_kernel and S >= 128 and not isinstance(
                window, jax.core.Tracer):
            # Pallas fast path (TPU target; interpret mode on CPU).  The
            # window must be static for the kernel; traced per-layer
            # windows (gemma2/hymba scans) use the jnp path.
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, causal=causal,
                                       window=int(window),
                                       softcap=cfg.attn_softcap)
            return out.reshape(B, S, h * hd) @ p["wo"].astype(cd), None

        def jnp_path(q, k, v):
            return _path(_sdpa_auto(
                q, k, v, q_pos, q_pos, causal=causal, window=window,
                softcap=cfg.attn_softcap, compute_dtype=cd,
                q_chunk=cfg.q_chunk, n_heads=cfg.n_heads), "jnp")

        if _fused_eligible(cfg, S, window, causal):
            # Positions are 0..S-1 here, as every no-cache caller passes.
            from repro.kernels import ops as kops

            def fused_path(q, k, v):
                return _path(kops.causal_flash_attention(q, k, v).reshape(
                    B, S, h * hd), "fused")

            out = jax.lax.platform_dependent(q, k, v, tpu=fused_path,
                                             default=jnp_path)
        else:
            out = jnp_path(q, k, v)
        return out @ p["wo"].astype(cd), None

    # decode: write S new token(s) into slots q_pos % Smax, attend over cache
    smax = cache.k.shape[1]
    slots = q_pos % smax                                       # [B,S]
    bidx = jnp.arange(B)[:, None]
    new_pos = cache.pos.at[bidx, slots].set(q_pos.astype(jnp.int32))
    if cache.quantized:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_k = cache.k.at[bidx, slots].set(kq)
        new_v = cache.v.at[bidx, slots].set(vq)
        new_ks = cache.k_scale.at[bidx, slots].set(ks)
        new_vs = cache.v_scale.at[bidx, slots].set(vs)
        k_full = _dequantize_kv(new_k, new_ks, cd)
        v_full = _dequantize_kv(new_v, new_vs, cd)
        new_cache = KVCache(new_k, new_v, new_pos, new_ks, new_vs)
    else:
        new_k = cache.k.at[bidx, slots].set(k.astype(cache.k.dtype))
        new_v = cache.v.at[bidx, slots].set(v.astype(cache.v.dtype))
        k_full, v_full = new_k.astype(cd), new_v.astype(cd)
        new_cache = KVCache(new_k, new_v, new_pos)
    out = _path(_sdpa(q, k_full, v_full, q_pos, new_pos,
                      causal=True, window=window, softcap=cfg.attn_softcap,
                      compute_dtype=cd), "jnp")
    return out @ p["wo"].astype(cd), new_cache


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------


def init_mlp(rng, d: int, d_ff: int, kind: str, dtype):
    r = jax.random.split(rng, 3)
    s = 1.0 / jnp.sqrt(d)
    p = {"w_up": (jax.random.normal(r[0], (d, d_ff)) * s).astype(dtype),
         "w_down": (jax.random.normal(r[1], (d_ff, d)) / jnp.sqrt(d_ff)).astype(dtype)}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(r[2], (d, d_ff)) * s).astype(dtype)
    return p


def mlp(p, x, kind: str) -> jax.Array:
    cd = x.dtype
    up = x @ p["w_up"].astype(cd)
    if kind == "swiglu":
        up = jax.nn.silu(x @ p["w_gate"].astype(cd)) * up
    elif kind == "geglu":
        up = jax.nn.gelu(x @ p["w_gate"].astype(cd), approximate=True) * up
    elif kind == "gelu":
        up = jax.nn.gelu(up, approximate=True)
    else:
        raise ValueError(f"unknown mlp kind {kind}")
    return up @ p["w_down"].astype(cd)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token CE; logits [B,S,V] (any dtype, upcast), labels [B,S]."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

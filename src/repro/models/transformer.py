"""Family builders: dense / moe / hybrid / vlm / audio transformers.

Every builder returns the same functional ``Model`` API (see model.py):

  init(rng)                          -> params pytree
  loss_fn(params, batch)             -> (loss, metrics)        [train_*]
  prefill(params, batch)             -> logits                 [prefill_*]
  init_cache(batch, max_slots)       -> decode cache pytree
  decode_step(params, cache, tok, pos) -> (logits, new cache)  [decode_*]

Layers are stacked with ``lax.scan`` over param pytrees whose leaves carry a
leading ``[L]`` dim (compile time is O(1) in depth -- llama3-405B's 126
layers lower as one scanned block).  Per-layer heterogeneity (gemma2's
local/global alternation, hymba's global-attention islands) rides along the
scan as a ``windows[L]`` array consumed inside the mask, so no unrolling or
lax.cond is needed.  ``cfg.remat`` wraps the block in jax.checkpoint
(full recompute policy) for the big training configs.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import (KVCache, attention, init_attn, init_embedding,
                                 init_kv_cache, init_mlp, init_rms_norm, mlp,
                                 rms_norm, sinusoidal_positions,
                                 softmax_cross_entropy)
from repro.models.moe import init_moe, moe_apply
from repro.models.ssm import (init_mamba, init_mamba_state, mamba_seq,
                              mamba_step)

# ---------------------------------------------------------------------------
# per-layer window schedule
# ---------------------------------------------------------------------------


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """windows[L]: 0 = full/global attention, >0 = sliding window."""
    L = cfg.n_layers
    if cfg.layer_pattern == "local_global" and cfg.window:
        w = [cfg.window if (i % 2 == 0) else 0 for i in range(L)]
    elif cfg.family == "hybrid" and cfg.window:
        # Hymba: global attention at first, middle and last layer only.
        glob = {0, L // 2, L - 1}
        w = [0 if i in glob else cfg.window for i in range(L)]
    elif cfg.window:
        w = [cfg.window] * L
    else:
        w = [0] * L
    return jnp.asarray(w, jnp.int32)


# ---------------------------------------------------------------------------
# decoder block (dense / moe / hybrid)
# ---------------------------------------------------------------------------


def init_block(rng, cfg: ModelConfig, dtype, *, kind: str, d_ff: int = 0):
    """kind: dense | moe | hybrid | cross (audio decoder)."""
    r = jax.random.split(rng, 6)
    p = {
        "ln1": init_rms_norm(cfg.d_model, dtype),
        "attn": init_attn(r[0], cfg, dtype),
        "ln2": init_rms_norm(cfg.d_model, dtype),
    }
    if kind == "moe":
        p["moe"] = init_moe(r[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp(r[1], cfg.d_model, d_ff or cfg.d_ff, cfg.mlp, dtype)
    if kind == "hybrid":
        p["mamba"] = init_mamba(r[2], cfg, dtype)
    if kind == "cross":
        p["ln_x"] = init_rms_norm(cfg.d_model, dtype)
        p["xattn"] = init_attn(r[3], cfg, dtype)
    return p


def block_apply(cfg: ModelConfig, p, x, q_pos, window, *, kind: str,
                cache: KVCache | None = None, ssm_state=None,
                enc_out=None, causal: bool = True):
    """Returns (x, new_cache, new_ssm_state, aux_loss)."""
    from repro.models.layers import BATCH_AXES, shard_hint
    sp = cfg.seq_shard_blocks and cache is None

    def _sp_resid(t):   # residual stream: sequence-sharded over "model"
        return shard_hint(t, BATCH_AXES, "model", None) if sp else t

    def _pin(t):        # stop XLA hoisting fp32 converts across this value
        return jax.lax.optimization_barrier(t) if cfg.barrier_block_inputs \
            else t

    # Megatron-SP: norms/residual/remat-saves live S-sharded (1/16 size).
    x = _sp_resid(x)
    h = _pin(rms_norm(x, p["ln1"], cfg.norm_eps, cfg.norm_cast_early))
    attn_out, new_cache = attention(
        cfg, p["attn"], h, q_pos, window=window, cache=cache,
        rope=cfg.rope != "none", causal=causal)
    if kind == "hybrid":
        if ssm_state is None:
            m_out = mamba_seq(cfg, p["mamba"], h)
            new_ssm = None
        else:
            m_out, new_ssm = mamba_step(cfg, p["mamba"], ssm_state, h[:, 0])
            m_out = m_out[:, None, :]
        attn_out = 0.5 * (attn_out + m_out)          # Hymba parallel fusion
    else:
        new_ssm = None
    x = _sp_resid(x + _sp_resid(attn_out))
    if kind == "cross":
        hx = rms_norm(x, p["ln_x"], cfg.norm_eps, cfg.norm_cast_early)
        x_out, _ = attention(cfg, p["xattn"], hx, q_pos, enc_out=enc_out,
                             rope=False)
        x = x + x_out
    h2 = _pin(rms_norm(x, p["ln2"], cfg.norm_eps, cfg.norm_cast_early))
    if kind == "moe":
        ff, aux = moe_apply(cfg, p["moe"], h2)
    else:
        ff, aux = mlp(p["mlp"], h2, cfg.mlp), jnp.zeros((), jnp.float32)
    return _sp_resid(x + _sp_resid(ff)), new_cache, new_ssm, aux


# ---------------------------------------------------------------------------
# stack runners (scan over layers)
# ---------------------------------------------------------------------------


def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


def run_stack(cfg: ModelConfig, stacked, x, q_pos, windows, *, kind: str,
              enc_out=None, causal: bool = True):
    """Train/prefill pass over L scanned layers.  Returns (x, aux_sum)."""

    def body_fn(p, x, w):
        # With no window configured every layer's is 0: pass it as a
        # Python 0, which the attention's dispatch can read at trace time.
        y, _, _, aux = block_apply(cfg, p, x, q_pos, w if cfg.window else 0,
                                   kind=kind, enc_out=enc_out, causal=causal)
        return y, aux

    body = _maybe_remat(body_fn, cfg)

    def step(carry, per):
        x, aux = carry
        p, w = per
        y, a = body(p, x, w)
        return (y, aux + a), None

    (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                               (stacked, windows))
    return x, aux


def run_stack_decode(cfg: ModelConfig, stacked, x, q_pos, windows, caches,
                     *, kind: str, ssm_states=None, enc_out=None):
    """One-token decode across L scanned layers; carries updated caches."""

    def step(x, per):
        if kind == "hybrid":
            p, w, cache, sstate = per
        else:
            p, w, cache = per
            sstate = None
        y, new_cache, new_sstate, _ = block_apply(
            cfg, p, x, q_pos, w, kind=kind, cache=cache, ssm_state=sstate,
            enc_out=enc_out)
        ys = (new_cache, new_sstate) if kind == "hybrid" else new_cache
        return y, ys

    xs = (stacked, windows, caches) if kind != "hybrid" else \
         (stacked, windows, caches, ssm_states)
    x, new = jax.lax.scan(step, x, xs)
    return x, new


# ---------------------------------------------------------------------------
# shared model scaffolding
# ---------------------------------------------------------------------------


def _embed_in(params, cfg, tokens):
    cd = jnp.dtype(cfg.compute_dtype)
    x = params["embed"][tokens].astype(cd)
    if cfg.name.startswith("gemma2"):
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), cd)   # gemma2 embeds scaled
    from repro.models.layers import BATCH_AXES, shard_hint
    return shard_hint(x, BATCH_AXES, None, None)


def _padded_vocab(cfg) -> int:
    return -(-cfg.vocab // 256) * 256


def _unembed(params, cfg, x):
    """Project to (padded) vocabulary.  Returns [..., Vp] with the padded
    tail pinned to -1e30 (invisible to softmax/argmax); callers on the
    public API slice back to cfg.vocab via _public_logits.  Padding to a
    multiple of 256 keeps the logits slab model-axis shardable for the
    odd-sized vocabs (whisper 51865, internvl 151655)."""
    from repro.models.layers import BATCH_AXES, shard_hint
    cd = x.dtype
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    table = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    V, Vp = cfg.vocab, _padded_vocab(cfg)
    if Vp != V:
        table = jnp.pad(table, ((0, 0), (0, Vp - V)))
    logits = x @ table.astype(cd)
    # keep the [B, S, V] slab batch- AND vocab-sharded: at 128k-256k vocabs
    # an unsharded logits tensor alone would overflow HBM
    logits = shard_hint(logits, BATCH_AXES, None, "model")
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.final_softcap)
    if Vp != V:
        pad_mask = jax.lax.broadcasted_iota(jnp.int32, (Vp,), 0) >= V
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)
    return shard_hint(logits, BATCH_AXES, None, "model")


def _public_logits(cfg, logits):
    return logits[..., : cfg.vocab] if _padded_vocab(cfg) != cfg.vocab \
        else logits


def _init_common(rng, cfg: ModelConfig, dtype):
    r = jax.random.split(rng, 3)
    p = {"embed": init_embedding(r[0], cfg.vocab, cfg.d_model, dtype),
         "ln_f": init_rms_norm(cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(r[1], (cfg.d_model, cfg.vocab))
                        / jnp.sqrt(cfg.d_model)).astype(dtype)
    return p


def _positions(batch: int, seq: int):
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))


# ---------------------------------------------------------------------------
# DENSE (gemma2 / chatglm3 / llama3) and VLM (internvl2 backbone)
# ---------------------------------------------------------------------------


def build_dense(cfg: ModelConfig, max_seq: int):
    dtype = jnp.dtype(cfg.param_dtype)
    windows = layer_windows(cfg)
    is_vlm = cfg.family == "vlm"

    def init(rng):
        r = jax.random.split(rng, 3)
        p = _init_common(r[0], cfg, dtype)
        p["layers"] = jax.vmap(
            lambda k: init_block(k, cfg, dtype, kind="dense")
        )(jax.random.split(r[1], cfg.n_layers))
        if is_vlm:
            p["projector"] = (jax.random.normal(r[2], (cfg.d_model, cfg.d_model))
                              / jnp.sqrt(cfg.d_model)).astype(dtype)
        return p

    def _forward(params, batch):
        tokens = batch["tokens"]
        x = _embed_in(params, cfg, tokens)
        if is_vlm:
            cd = x.dtype
            patches = batch["patches"].astype(cd) @ params["projector"].astype(cd)
            x = jnp.concatenate([patches, x], axis=1)
        q_pos = _positions(x.shape[0], x.shape[1])
        x, aux = run_stack(cfg, params["layers"], x, q_pos, windows,
                           kind="dense")
        return _unembed(params, cfg, x), aux

    def loss_fn(params, batch):
        logits, aux = _forward(params, batch)
        tokens = batch["tokens"]
        n_txt = tokens.shape[1]
        logits = logits[:, -n_txt:-1] if not is_vlm else logits[:, -n_txt - 1:-1]
        labels = tokens[:, 1:] if not is_vlm else tokens
        loss = softmax_cross_entropy(logits, labels) + aux
        return loss, {"loss": loss, "aux": aux}

    def prefill(params, batch):
        logits, _ = _forward(params, batch)
        return _public_logits(cfg, logits)

    def init_cache(batch_size: int, max_slots: int):
        cd = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)
        kv = jax.vmap(lambda _: init_kv_cache(
            batch_size, max_slots, cfg.n_kv_heads, cfg.head_dim, cd)
        )(jnp.arange(cfg.n_layers))
        return {"kv": kv}

    def decode_step(params, cache, tok, pos):
        x = _embed_in(params, cfg, tok[:, None])
        q_pos = pos[:, None].astype(jnp.int32)
        x, new_kv = run_stack_decode(cfg, params["layers"], x, q_pos, windows,
                                     cache["kv"], kind="dense")
        logits = _public_logits(cfg, _unembed(params, cfg, x))
        return logits[:, 0], {"kv": new_kv}

    return init, loss_fn, prefill, init_cache, decode_step


# ---------------------------------------------------------------------------
# MOE (deepseek-moe-16b / kimi-k2): leading dense layer(s) + scanned MoE stack
# ---------------------------------------------------------------------------


def build_moe(cfg: ModelConfig, max_seq: int):
    dtype = jnp.dtype(cfg.param_dtype)
    n_moe = cfg.n_layers - cfg.n_dense_layers
    windows = layer_windows(cfg)[cfg.n_dense_layers:]

    def init(rng):
        r = jax.random.split(rng, 3)
        p = _init_common(r[0], cfg, dtype)
        if cfg.n_dense_layers:
            p["dense_layers"] = jax.vmap(
                lambda k: init_block(k, cfg, dtype, kind="dense",
                                     d_ff=cfg.dense_d_ff)
            )(jax.random.split(r[1], cfg.n_dense_layers))
        p["layers"] = jax.vmap(
            lambda k: init_block(k, cfg, dtype, kind="moe")
        )(jax.random.split(r[2], n_moe))
        return p

    def _run_dense_prefix(params, x, q_pos):
        if not cfg.n_dense_layers:
            return x, jnp.zeros((), jnp.float32)
        return run_stack(cfg, params["dense_layers"], x, q_pos,
                         jnp.zeros((cfg.n_dense_layers,), jnp.int32),
                         kind="dense")

    def _forward(params, batch):
        tokens = batch["tokens"]
        x = _embed_in(params, cfg, tokens)
        q_pos = _positions(*tokens.shape)
        x, aux0 = _run_dense_prefix(params, x, q_pos)
        x, aux = run_stack(cfg, params["layers"], x, q_pos, windows, kind="moe")
        return _unembed(params, cfg, x), aux0 + aux

    def loss_fn(params, batch):
        logits, aux = _forward(params, batch)
        tokens = batch["tokens"]
        loss = softmax_cross_entropy(logits[:, :-1], tokens[:, 1:]) + aux
        return loss, {"loss": loss, "aux": aux}

    def prefill(params, batch):
        return _public_logits(cfg, _forward(params, batch)[0])

    def init_cache(batch_size: int, max_slots: int):
        cd = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)
        mk = lambda n: jax.vmap(lambda _: init_kv_cache(
            batch_size, max_slots, cfg.n_kv_heads, cfg.head_dim, cd)
        )(jnp.arange(n))
        cache = {"kv": mk(n_moe)}
        if cfg.n_dense_layers:
            cache["kv_dense"] = mk(cfg.n_dense_layers)
        return cache

    def decode_step(params, cache, tok, pos):
        x = _embed_in(params, cfg, tok[:, None])
        q_pos = pos[:, None].astype(jnp.int32)
        new_cache = dict(cache)
        if cfg.n_dense_layers:
            x, new_dense = run_stack_decode(
                cfg, params["dense_layers"], x, q_pos,
                jnp.zeros((cfg.n_dense_layers,), jnp.int32),
                cache["kv_dense"], kind="dense")
            new_cache["kv_dense"] = new_dense
        x, new_kv = run_stack_decode(cfg, params["layers"], x, q_pos, windows,
                                     cache["kv"], kind="moe")
        new_cache["kv"] = new_kv
        logits = _public_logits(cfg, _unembed(params, cfg, x))
        return logits[:, 0], new_cache

    return init, loss_fn, prefill, init_cache, decode_step


# ---------------------------------------------------------------------------
# HYBRID (hymba: parallel attention + mamba heads)
# ---------------------------------------------------------------------------


def build_hybrid(cfg: ModelConfig, max_seq: int):
    dtype = jnp.dtype(cfg.param_dtype)
    windows = layer_windows(cfg)

    def init(rng):
        r = jax.random.split(rng, 2)
        p = _init_common(r[0], cfg, dtype)
        p["layers"] = jax.vmap(
            lambda k: init_block(k, cfg, dtype, kind="hybrid")
        )(jax.random.split(r[1], cfg.n_layers))
        return p

    def _forward(params, batch):
        tokens = batch["tokens"]
        x = _embed_in(params, cfg, tokens)
        q_pos = _positions(*tokens.shape)
        x, aux = run_stack(cfg, params["layers"], x, q_pos, windows,
                           kind="hybrid")
        return _unembed(params, cfg, x), aux

    def loss_fn(params, batch):
        logits, aux = _forward(params, batch)
        tokens = batch["tokens"]
        loss = softmax_cross_entropy(logits[:, :-1], tokens[:, 1:]) + aux
        return loss, {"loss": loss, "aux": aux}

    def prefill(params, batch):
        return _public_logits(cfg, _forward(params, batch)[0])

    def init_cache(batch_size: int, max_slots: int):
        cd = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)
        kv = jax.vmap(lambda _: init_kv_cache(
            batch_size, max_slots, cfg.n_kv_heads, cfg.head_dim, cd)
        )(jnp.arange(cfg.n_layers))
        ssm = jax.vmap(lambda _: init_mamba_state(cfg, batch_size, cd)
                       )(jnp.arange(cfg.n_layers))
        return {"kv": kv, "ssm": ssm}

    def decode_step(params, cache, tok, pos):
        x = _embed_in(params, cfg, tok[:, None])
        q_pos = pos[:, None].astype(jnp.int32)
        x, new = run_stack_decode(cfg, params["layers"], x, q_pos, windows,
                                  cache["kv"], kind="hybrid",
                                  ssm_states=cache["ssm"])
        new_kv, new_ssm = new
        logits = _public_logits(cfg, _unembed(params, cfg, x))
        return logits[:, 0], {"kv": new_kv, "ssm": new_ssm}

    return init, loss_fn, prefill, init_cache, decode_step


# ---------------------------------------------------------------------------
# AUDIO (whisper-tiny): stub-frontend encoder + cross-attending decoder
# ---------------------------------------------------------------------------


def build_audio(cfg: ModelConfig, max_seq: int):
    dtype = jnp.dtype(cfg.param_dtype)
    dec_windows = layer_windows(cfg)

    def init(rng):
        r = jax.random.split(rng, 4)
        p = _init_common(r[0], cfg, dtype)
        p["enc_layers"] = jax.vmap(
            lambda k: init_block(k, cfg, dtype, kind="dense")
        )(jax.random.split(r[1], cfg.n_enc_layers))
        p["enc_ln_f"] = init_rms_norm(cfg.d_model, dtype)
        p["dec_layers"] = jax.vmap(
            lambda k: init_block(k, cfg, dtype, kind="cross")
        )(jax.random.split(r[2], cfg.n_layers))
        p["pos_emb"] = (jax.random.normal(r[3], (max_seq, cfg.d_model))
                        * 0.01).astype(dtype)
        return p

    def encode(params, frames):
        cd = jnp.dtype(cfg.compute_dtype)
        x = frames.astype(cd)
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model, cd)[None]
        q_pos = _positions(x.shape[0], x.shape[1])
        x, _ = run_stack(cfg, params["enc_layers"], x, q_pos,
                         jnp.zeros((cfg.n_enc_layers,), jnp.int32),
                         kind="dense", causal=False)
        return rms_norm(x, params["enc_ln_f"], cfg.norm_eps)

    def _decode_seq(params, enc_out, tokens):
        x = _embed_in(params, cfg, tokens)
        S = tokens.shape[1]
        x = x + params["pos_emb"][:S].astype(x.dtype)[None]
        q_pos = _positions(*tokens.shape)
        x, aux = run_stack(cfg, params["dec_layers"], x, q_pos, dec_windows,
                           kind="cross", enc_out=enc_out)
        return _unembed(params, cfg, x), aux

    def loss_fn(params, batch):
        enc_out = encode(params, batch["frames"])
        logits, aux = _decode_seq(params, enc_out, batch["tokens"])
        loss = softmax_cross_entropy(logits[:, :-1], batch["tokens"][:, 1:]) + aux
        return loss, {"loss": loss, "aux": aux}

    def prefill(params, batch):
        enc_out = encode(params, batch["frames"])
        return _public_logits(cfg, _decode_seq(params, enc_out,
                                               batch["tokens"])[0])

    def init_cache(batch_size: int, max_slots: int):
        cd = jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)
        kv = jax.vmap(lambda _: init_kv_cache(
            batch_size, max_slots, cfg.n_kv_heads, cfg.head_dim, cd)
        )(jnp.arange(cfg.n_layers))
        enc_out = jnp.zeros((batch_size, cfg.enc_frames, cfg.d_model),
                            jnp.dtype(cfg.compute_dtype))
        return {"kv": kv, "enc_out": enc_out}

    def decode_step(params, cache, tok, pos):
        x = _embed_in(params, cfg, tok[:, None])
        x = x + params["pos_emb"][pos].astype(x.dtype)[:, None, :]
        q_pos = pos[:, None].astype(jnp.int32)
        x, new_kv = run_stack_decode(cfg, params["dec_layers"], x, q_pos,
                                     dec_windows, cache["kv"], kind="cross",
                                     enc_out=cache["enc_out"])
        logits = _public_logits(cfg, _unembed(params, cfg, x))
        return logits[:, 0], {"kv": new_kv, "enc_out": cache["enc_out"]}

    return init, loss_fn, prefill, init_cache, decode_step, encode

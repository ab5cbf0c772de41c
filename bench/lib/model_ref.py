"""Plain reference of one data-parallel training step of a VLM decoder
(internvl2-1b's projector and Qwen2-style language decoder), importing
nothing of the program under test.

Forward: image patches through a linear projector, prepended to the
embedded text; L pre-norm blocks of RMSNorm, grouped-query causal
attention with full rotary embeddings (half-split rotation), and a SwiGLU
feed-forward; a final RMSNorm and the output head (the embedding's
transpose where ``tie_embeddings``, else a matrix of its own).  The loss
is the mean next-token cross-entropy of the text tokens, the last image
position predicting the first text token.  AdamW with decoupled weight decay,
global-norm clipping and a linear-warmup cosine schedule.

Precision: float32 with ``Precision.HIGHEST`` products (on a TPU a float32
product otherwise runs in bfloat16).  ``fp8=True`` is the control: every
product's operands are rounded to float8 e4m3 with a per-tensor scale,
the step below the configuration's bfloat16 compute.  The gradient is
accumulated over blocks of ``block`` sequences, and AdamW's moments wait
on the host while the blocks run, so that the step fits on one chip
beside its parameters.
"""
from __future__ import annotations

import functools
import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


# --------------------------------------------------------------------------
# weights, made by the benchmark from the seed
# --------------------------------------------------------------------------


def param_shapes(m: dict) -> dict:
    """The parameter tree of configuration ``m``: name -> shape.  Stacked
    layer weights carry a leading [n_layers] axis."""
    d, L, V = m["d_model"], m["n_layers"], m["vocab"]
    qd, kd, ff = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"], m["d_ff"]
    head = {} if m["tie_embeddings"] else {"unembed": (d, V)}
    return {
        "embed": (V, d), "ln_f": (d,), "projector": (d, d), **head,
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, qd), "wk": (L, d, kd), "wv": (L, d, kd),
                     "wo": (L, qd, d)},
            "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                    "w_down": (L, ff, d)},
        },
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_weights(m: dict, key) -> dict:
    """Seeded float32 weights: norm scales 1, the embedding N(0, 0.02^2),
    every matrix N(0, 1 / fan_in).  Trace under ``jax.jit``: one call
    makes the whole tree on the device."""
    shapes = param_shapes(m)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]]
    out = []
    for i, (path, shape) in enumerate(zip(paths, leaves)):
        k = jax.random.fold_in(key, i)
        if "ln" in path:
            out.append(jnp.ones(shape, jnp.float32))
        elif path == "['embed']":
            out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        else:
            out.append(jax.random.normal(k, shape, jnp.float32)
                       / math.sqrt(shape[-2]))
    return jax.tree.unflatten(tree, out)


def seed_key(seed: int):
    """A PRNG key from a run seed of any size (fold to 32 bits first)."""
    return jax.random.PRNGKey(int(np.random.SeedSequence(seed)
                                  .generate_state(1)[0]))


# --------------------------------------------------------------------------
# forward and loss
# --------------------------------------------------------------------------


def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale; gradients pass
    straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x [B, S, H, D]: rotate (first half, second half) pairs by position."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # [S, half]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(m, fp8, x, p):
    B, S, d = x.shape
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _rms(x, p["ln1"], m["norm_eps"])
    q = _mm("bsd,de->bse", h, p["attn"]["wq"], fp8).reshape(B, S, H, hd)
    k = _mm("bsd,de->bse", h, p["attn"]["wk"], fp8).reshape(B, S, K, hd)
    v = _mm("bsd,de->bse", h, p["attn"]["wv"], fp8).reshape(B, S, K, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    q = q.reshape(B, S, K, H // K, hd)
    s = _mm("bqkgh,bskh->bkgqs", q, k, fp8) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgqs,bskh->bqkgh", a, v, fp8).reshape(B, S, H * hd)
    x = x + _mm("bse,ed->bsd", o, p["attn"]["wo"], fp8)
    h = _rms(x, p["ln2"], m["norm_eps"])
    g = _mm("bsd,df->bsf", h, p["mlp"]["w_gate"], fp8)
    u = _mm("bsd,df->bsf", h, p["mlp"]["w_up"], fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["mlp"]["w_down"], fp8)


def loss_sum(m: dict, fp8: bool, params, tokens, patches):
    """Summed cross-entropy of the text tokens of a block of sequences."""
    P = m["n_patches"]
    x = jnp.concatenate([
        _mm("bpd,de->bpe", patches, params["projector"], fp8),
        params["embed"][tokens]], axis=1)
    body = jax.checkpoint(partial(_block, m, fp8))
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x, params["layers"])
    h = _rms(x, params["ln_f"], m["norm_eps"])[:, P - 1:-1]
    if m["tie_embeddings"]:
        logits = _mm("bsd,vd->bsv", h, params["embed"], fp8)
    else:
        logits = _mm("bsd,dv->bsv", h, params["unembed"], fp8)
    gold = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


# --------------------------------------------------------------------------
# AdamW
# --------------------------------------------------------------------------


def lr_at(o: dict, t: int) -> float:
    """Linear warmup to ``lr`` over ``warmup_steps``, then cosine to
    ``min_lr_ratio * lr`` at ``total_steps`` (1-based step ``t``)."""
    if t < o["warmup_steps"]:
        return o["lr"] * t / max(o["warmup_steps"], 1)
    frac = min(max((t - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    r = o["min_lr_ratio"]
    return o["lr"] * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * frac)))


def adamw_update(o: dict, grads, params, mom, vel, lr, bc1, bc2):
    """One AdamW step on float32 trees at learning rate ``lr`` with bias
    corrections ``bc1 = 1 - b1^t``, ``bc2 = 1 - b2^t``."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = o["b1"], o["b2"]

    def one(g, p, mo, ve):
        g = g * scale
        mo = b1 * mo + (1 - b1) * g
        ve = b2 * ve + (1 - b2) * g * g
        delta = (mo / bc1) / (jnp.sqrt(ve / bc2) + o["eps"]) \
            + o["weight_decay"] * p
        return p - lr * delta, mo, ve

    out = jax.tree.map(one, grads, params, mom, vel)
    pick = lambda i: jax.tree.map(lambda t3: t3[i], out,
                                  is_leaf=lambda t3: isinstance(t3, tuple))
    return pick(0), pick(1), pick(2)


# --------------------------------------------------------------------------
# norms compared, leaf by leaf (each layer of a stacked weight is a leaf)
# --------------------------------------------------------------------------


def leaf_norms(tree):
    """L2 norm of every leaf, one per layer for the stacked weights, in a
    fixed order.  Traceable."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = leaf.astype(jnp.float32)
        if jax.tree_util.keystr(path).startswith("['layers']"):
            out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim)))))
        else:
            out.append(jnp.sqrt(jnp.sum(x * x))[None])
    return jnp.concatenate(out)


# --------------------------------------------------------------------------
# the reference run: three steps on the given batches
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(m_json: str, o_json: str, fp8: bool, device) -> dict:
    """The reference's jitted programs for one configuration, precision
    and device, built once a process: every later seed, control and fault
    of the process reuses them."""
    m, o = json.loads(m_json), json.loads(o_json)
    one = jax.sharding.SingleDeviceSharding(device)
    return {
        "put": partial(jax.device_put, device=one),
        "weights": jax.jit(partial(make_weights, m), out_shardings=one),
        "grad_block": jax.jit(jax.value_and_grad(partial(loss_sum, m, fp8))),
        "add": jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                       donate_argnums=0),
        "scale": jax.jit(lambda g, n: jax.tree.map(lambda x: x / n, g),
                         donate_argnums=0),
        "update": jax.jit(partial(adamw_update, o),
                          donate_argnums=(0, 1, 2, 3)),
        "norms": jax.jit(leaf_norms),
        "change": jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b))),
    }


def reference_steps(m: dict, o: dict, seed: int, batches: list[dict],
                    block: int, fp8: bool = False, device=None) -> dict:
    """Three (or ``len(batches)``) reference steps from the seeded weights.

    Returns each step's mean loss, the per-leaf norms of the first
    clipped gradient (``m_1 / (1 - b1)``), the per-leaf norms of the
    parameters' change after the last step, and the per-leaf norms of
    the first raw gradient (which leaves have a gradient at all)."""
    f = _programs(json.dumps(m, sort_keys=True), json.dumps(o, sort_keys=True),
                  fp8, device or jax.devices()[0])
    params = f["weights"](seed_key(seed))
    moments = None          # AdamW's (m, v), on the host between updates
    losses = []
    out: dict = {}
    for t, batch in enumerate(batches, start=1):
        B = batch["tokens"].shape[0]
        total, grads = 0.0, None
        for lo in range(0, B, block):
            val, g = f["grad_block"](params,
                                     f["put"](batch["tokens"][lo:lo + block]),
                                     f["put"](batch["patches"][lo:lo + block]))
            total += float(val)
            grads = g if grads is None else f["add"](grads, g)
            del g
        n = B * batch["tokens"].shape[1]
        grads = f["scale"](grads, float(n))
        losses.append(total / n)
        if t == 1:
            out["grad_raw_norms"] = np.asarray(f["norms"](grads))
        if moments is None:
            mom = jax.tree.map(jnp.zeros_like, params)
            vel = jax.tree.map(jnp.zeros_like, params)
        else:
            mom, vel = f["put"](moments)
        params, mom, vel = f["update"](grads, params, mom, vel, lr_at(o, t),
                                       1 - o["b1"] ** t, 1 - o["b2"] ** t)
        if t == 1:
            out["grad_norms"] = np.asarray(f["norms"](mom)) / (1 - o["b1"])
        moments = jax.device_get((mom, vel))
        del mom, vel
    out["change_norms"] = np.asarray(f["change"](
        params, f["weights"](seed_key(seed))))
    out["losses"] = losses
    return out


def worst_gap(got: np.ndarray, ref: np.ndarray,
              keep: np.ndarray | None = None) -> tuple[float, int]:
    """Largest |got - ref| over leaves, each against max(ref of that
    leaf, median ref); returns (gap, leaf index)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    idx = np.arange(len(ref)) if keep is None else np.flatnonzero(keep)
    floor = np.median(ref[idx])
    gaps = np.abs(got[idx] - ref[idx]) / np.maximum(ref[idx], floor)
    i = int(np.argmax(gaps))
    return float(gaps[i]), int(idx[i])

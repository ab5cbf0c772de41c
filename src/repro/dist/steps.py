"""Train / serve step factories wiring models + optimizer + collectives.

* :func:`make_train_step`     — single-program step (the pjit production
  path: gradient sync is implicit in GSPMD), with optional gradient
  accumulation from ``AdamWConfig.grad_accum_steps``;
* :func:`make_rar_train_step` — the paper-faithful data-parallel step: the
  batch splits over a 1-D ``"data"`` mesh, each worker takes grads on its
  shard, and the full flattened gradient is exchanged with the explicit
  ring-all-reduce of :mod:`repro.dist.rar` (one ``d``-sized ring per
  iteration, exactly the exchange §3 models) before a replicated AdamW
  update.  Equivalent to :func:`make_train_step` on the concatenated batch
  up to ring-order float reassociation;
* :func:`make_serve_step`     — one greedy decode step against the cache.

All returned functions are pure and jit-ready; metrics are scalar dicts
(``loss``/``grad_norm``/``lr`` at minimum).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.dist.rar import ring_all_reduce
from repro.models.model import Model
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig

RING_AXIS = "data"


def _grads_and_loss(model: Model, ocfg: AdamWConfig,
                    params, batch) -> tuple:
    """(grads, loss) on one batch, honouring ``grad_accum_steps``.

    Accumulation scans over A microbatches (axis-0 splits) and averages —
    peak activation memory scales ~1/A while the averaged gradient matches
    the full-batch one up to float reassociation.
    """
    grad_fn = jax.value_and_grad(model.loss_fn, has_aux=True)
    A = max(int(ocfg.grad_accum_steps), 1)
    if A == 1:
        (loss, _aux), grads = grad_fn(params, batch)
        return grads, loss

    def split(leaf):
        B = leaf.shape[0]
        if B % A != 0:
            raise ValueError(
                f"global batch {B} must be divisible by "
                f"grad_accum_steps={A}")
        return leaf.reshape((A, B // A) + leaf.shape[1:])

    micro = jax.tree.map(split, batch)

    def body(carry, mb):
        gsum, lsum = carry
        (loss, _aux), g = grad_fn(params, mb)
        return (jax.tree.map(jnp.add, gsum, g), lsum + loss), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (gsum, lsum), _ = jax.lax.scan(body, (zeros, jnp.zeros((), jnp.float32)),
                                   micro)
    return jax.tree.map(lambda g: g / A, gsum), lsum / A


def make_train_step(model: Model, ocfg: AdamWConfig) -> Callable:
    """``(params, opt, batch) -> (params, opt, metrics)``, single program.

    Under pjit the data/model parallelism comes from the argument shardings
    (``repro.dist.sharding``); XLA inserts the gradient collectives.
    """

    def step(params, opt, batch):
        """One optimizer step on one global batch."""
        grads, loss = _grads_and_loss(model, ocfg, params, batch)
        new_params, new_opt, om = adamw.apply(ocfg, grads, params, opt)
        return new_params, new_opt, {"loss": loss, **om}

    return step


def replicate(tree, mesh):
    """``tree`` placed replicated over ``mesh`` -- the layout the RAR
    step returns its params and optimizer state in, so placing the
    initial ones this way spares a second compile at step 1.  The
    input buffers are donated (no second copy is held)."""
    return jax.device_put(tree, NamedSharding(mesh, P()), donate=True)


def make_rar_train_step(model: Model, ocfg: AdamWConfig, mesh) -> Callable:
    """Explicit ring-all-reduce data-parallel step over ``mesh``.

    ``mesh`` must be 1-D over axis ``"data"`` (any device subset — the
    scheduler launcher builds it from exactly the GPUs a placement
    assigned).  Params and optimizer state are replicated; the batch's
    leading dim must be divisible by the ring width ``w``.  Per step each worker
    ring-exchanges the full flattened gradient — ``2 d (w-1)/w`` bytes,
    the §3 exchange volume — then applies an identical AdamW update, so
    parameters stay bitwise replicated without a broadcast.
    """
    if RING_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh must carry a {RING_AXIS!r} axis, "
                         f"got {mesh.axis_names}")
    w = int(dict(zip(mesh.axis_names, mesh.devices.shape))[RING_AXIS])

    def local_step(params, opt, batch):
        """Per-worker body: local grads, ring exchange, replicated update."""
        grads, loss = _grads_and_loss(model, ocfg, params, batch)
        if w > 1:
            gvec, unravel = ravel_pytree(grads)
            grads = unravel(ring_all_reduce(gvec, RING_AXIS) / w)
            loss = jax.lax.psum(loss, RING_AXIS) / w
        new_params, new_opt, om = adamw.apply(ocfg, grads, params, opt)
        return new_params, new_opt, {"loss": loss, **om}

    # check_vma=False: the replication of the ppermute-built update is by
    # construction (identical inputs -> identical arithmetic on every
    # worker), which shard_map's varying-axes analysis cannot prove.
    mapped = jax.shard_map(local_step, mesh=mesh,
                           in_specs=(P(), P(), P(RING_AXIS)),
                           out_specs=(P(), P(), P()),
                           check_vma=False)
    # Params and optimizer state are donated: the step's outputs reuse
    # their buffers, so a step holds one copy of each, not two.
    return jax.jit(mapped, donate_argnums=(0, 1))


def make_serve_step(model: Model) -> Callable:
    """``(params, cache, tok, pos) -> (next_tok, logits, cache)``: one
    greedy decode step (argmax sampling, deterministic)."""

    def serve(params, cache, tok, pos):
        """Decode one token per sequence and write it into the cache."""
        logits, new_cache = model.decode_step(params, cache, tok, pos)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, new_cache

    return serve

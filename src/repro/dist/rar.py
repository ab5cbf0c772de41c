"""Explicit ring-all-reduce collectives (paper §3, Fig. 1).

A ring of ``w`` workers exchanges a ``d``-sized gradient in two phases of
``w - 1`` steps each, built here from :func:`jax.lax.ppermute` so the
compiled HLO contains exactly ``2(w - 1)`` collective-permutes:

* **Share-Reduce** (:func:`ring_reduce_scatter`) — each worker ends up
  owning the fully reduced ``1/w`` chunk with its own index;
* **Share-Only** (:func:`ring_all_gather`) — the reduced chunks circulate
  until every worker holds the full result.

Per iteration each worker sends/receives ``2 d (w - 1) / w`` bytes
(:func:`exchange_bytes_per_worker`) — asymptotically independent of ``w``,
the bandwidth-optimality argument of §3 that makes RAR the substrate worth
scheduling (contrast the server-worker architecture's ``2 w d`` per server).

All three collectives are meant to be called *inside* ``jax.shard_map``
over a 1-D mesh axis (conventionally ``"data"``); chunking flattens the
input and zero-pads it to a multiple of ``w``, so arbitrary tensor sizes
work.  ``w == 1`` degenerates to the identity (no communication).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def axis_size(axis_name: str) -> int:
    """Static size ``w`` of the mapped ring axis (shard_map body scope)."""
    return int(jax.lax.axis_size(axis_name))


def exchange_bytes_per_worker(d: float, w: int) -> float:
    """Bytes each worker sends per RAR iteration for a ``d``-byte gradient.

    §3: ``2 d (w - 1) / w`` — each of the ``2(w - 1)`` ring steps moves a
    ``d / w`` chunk.  The degenerate single-worker ring exchanges nothing.
    """
    if w < 1:
        raise ValueError(f"ring width must be >= 1, got {w}")
    if w == 1:
        return 0.0
    return 2.0 * d * (w - 1) / w


def _ring_chunks(x: jax.Array, w: int) -> tuple[jax.Array, int]:
    """Flatten ``x`` and zero-pad it to ``w`` equal chunks of ``m``
    elements.  Returns the flat ``[w * m]`` vector and ``m``; chunk ``c``
    is ``flat[c * m : (c + 1) * m]``."""
    flat = x.reshape(-1)
    m = -(-flat.size // w)
    if m * w != flat.size:
        flat = jnp.pad(flat, (0, m * w - flat.size))
    return flat, m


def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Share-Reduce phase: ``w - 1`` ppermute steps around the ring.

    Each worker contributes its local ``x``; worker ``i`` returns the fully
    reduced chunk ``i`` of the (zero-padded) flattened sum — a 1-D array of
    ``ceil(x.size / w)`` elements.
    """
    w = axis_size(axis_name)
    flat, m = _ring_chunks(x, w)
    if w == 1:
        return flat
    i = jax.lax.axis_index(axis_name)
    # send "left" (j -> j-1): the partial for chunk c starts at worker c-1
    # and accumulates one local contribution per hop until worker c owns it.
    left = [(j, (j - 1) % w) for j in range(w)]

    def local_chunk(c):
        """This worker's contribution for (traced) chunk index ``c``: a
        1-D dynamic slice.  (Slicing row ``c`` of a ``[w, m]`` array
        instead took the TPU compiler minutes at lane-aligned ``m`` of a
        billion-parameter gradient.)"""
        return jax.lax.dynamic_slice_in_dim(flat, (c % w) * m, m)

    partial = local_chunk(i + 1)
    for t in range(w - 1):
        partial = jax.lax.ppermute(partial, axis_name, left)
        partial = partial + local_chunk(i + t + 2)
    return partial


def ring_all_gather(chunk: jax.Array, axis_name: str) -> jax.Array:
    """Share-Only phase: ``w - 1`` ppermute steps circulate reduced chunks.

    Worker ``i`` holds logical chunk ``i`` (the :func:`ring_reduce_scatter`
    convention); every worker returns the concatenation of all ``w`` chunks
    in index order, shape ``[w * chunk.shape[0], ...]``.
    """
    w = axis_size(axis_name)
    if w == 1:
        return chunk
    i = jax.lax.axis_index(axis_name)
    left = [(j, (j - 1) % w) for j in range(w)]
    m = chunk.size

    def put(out, part, c):
        """Write ``part`` as chunk ``c`` of the flat result (1-D, as in
        ``ring_reduce_scatter``: row writes into ``[w, m]`` made the TPU
        compiler take over ten minutes on a full-width training step)."""
        return jax.lax.dynamic_update_slice_in_dim(
            out, part.reshape(-1), (c % w) * m, axis=0)

    out = put(jnp.zeros((w * m,), chunk.dtype), chunk, i)
    buf = chunk
    for t in range(w - 1):
        buf = jax.lax.ppermute(buf, axis_name, left)
        out = put(out, buf, i + t + 1)
    return out.reshape((w * chunk.shape[0],) + chunk.shape[1:])


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Full RAR: Share-Reduce then Share-Only, ``2(w - 1)`` ppermutes total.

    Returns the elementwise sum of ``x`` across the ring — numerically a
    ring-ordered reassociation of :func:`jax.lax.psum` — with the input's
    shape and dtype.
    """
    w = axis_size(axis_name)
    if w == 1:
        return x
    chunk = ring_reduce_scatter(x, axis_name)
    full = ring_all_gather(chunk, axis_name)
    return full[: x.size].reshape(x.shape)

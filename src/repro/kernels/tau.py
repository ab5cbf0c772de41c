"""Pallas kernel for the integer half of the Eq. (6)-(8) stack reduction.

The contention model's hot loop scores stacks of candidate placements
Y [C, J, S]: per candidate, the straddle matrix (Eq. 6), the per-server
straddler counts, each job's contention level p (a max over its straddled
servers) and its server spread n_srv.  Those are the O(C J S) part of
:func:`repro.core.contention.stack_model`; this kernel fuses them into one
VMEM pass per candidate in int32, where they are exact.  The O(C J)
Eq. (7)-(8) float terms built on (p, n_srv) stay in float64 on the host,
so the model the caller gets back -- tau, phi and every term -- is the
NumPy engine's to the bit (the TPU has no IEEE float64).  Heterogeneous
clusters change only those host float terms, so one kernel serves both.

On CPU the kernel runs in Pallas interpret mode (opt-in via
:func:`repro.core.contention.tau_backend`); on TPU it lowers through
Mosaic.  Its sibling :mod:`repro.kernels.placement` fuses the columnar
placement engine's per-step reductions the same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _counts_kernel(y_ref, g_ref, p_ref, n_ref):
    """One candidate: Y [1, J, S], G [1, J, 1] -> p, n_srv [1, J, 1]."""
    y = y_ref[...]
    pos = y > 0
    straddle = pos & (y < g_ref[...])                # Eq. (6) straddling
    per_server = jnp.sum(straddle.astype(jnp.int32), axis=1, keepdims=True)
    p_ref[...] = jnp.max(jnp.where(straddle, per_server, 0), axis=2,
                         keepdims=True)
    n_ref[...] = jnp.sum(pos.astype(jnp.int32), axis=2, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _stack_counts_jit(Y, G, *, interpret):
    """``Y`` [C, J, S] int32, ``G`` [C, J, 1] or [1, J, 1] int32."""
    C, J, S = Y.shape
    # The candidate axis is the grid; blocks span whole (J, S) / (J, 1)
    # trailing dims, which is tile-legal at any J and S.
    g_idx = (lambda c: (c, 0, 0)) if G.shape[0] == C else (lambda c: (0, 0, 0))
    col = jax.ShapeDtypeStruct((C, J, 1), jnp.int32)
    p, n_srv = pl.pallas_call(
        _counts_kernel,
        grid=(C,),
        in_specs=[pl.BlockSpec((1, J, S), lambda c: (c, 0, 0)),
                  pl.BlockSpec((1, J, 1), g_idx)],
        out_specs=[pl.BlockSpec((1, J, 1), lambda c: (c, 0, 0))] * 2,
        out_shape=[col, col],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(Y, G)
    return p[:, :, 0], n_srv[:, :, 0]


def stack_counts(G: np.ndarray, Y: np.ndarray,
                 interpret: bool | None = None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Kernel-backed Eq. (6) reduction: ``(p, n_srv)``, int64 [C, J].

    ``Y`` [C, J, S] is the (already masked) candidate stack and ``G`` the
    per-job GPU counts, shared across the stack ([J]) or per candidate
    ([C, J], the columnar branch-stack layout).  ``interpret`` defaults to
    Pallas interpret mode on CPU backends."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    G = np.asarray(G)
    if G.ndim not in (1, 2):
        raise ValueError(f"G must be [J] or [C, J], got shape {G.shape}")
    G3 = (G if G.ndim == 2 else G[None, :])[:, :, None].astype(np.int32)
    with jax.enable_x64(False):
        p, n_srv = _stack_counts_jit(np.asarray(Y, dtype=np.int32), G3,
                                     interpret=bool(interpret))
    return np.asarray(p, dtype=np.int64), np.asarray(n_srv, dtype=np.int64)

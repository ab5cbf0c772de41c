"""Device programs for the columnar placement engine: pick statistics and
probe scoring, in int32/float32 with exact host re-checks.

The columnar engine (:class:`repro.core.columnar.ColumnarPlacement`)
advances every (theta, kappa) branch of the SJF-BCO forest by one job per
step.  Its per-step array work -- the Eq. (16) feasibility pools
(``U + rho/u <= theta + 1e-9``), the per-server busy/feasible-count
reductions behind the FA-FFP/LBSGF picks, and the Eq. (6)-(8) tau/rho
scoring of the probed candidates -- is a pile of small dense ops over
``[rows, N]`` operands.  This module fuses each half into ONE program:

  * :func:`pick_orders` -- pool threshold counts at each work item's
    extreme thetas, per-server busy sums (one 0/1 membership matmul),
    feasible-slot counts and the FA-FFP best server; the stable pick
    *rankings* then run host-side with NumPy sorts;
  * :func:`score_probes` -- Eq. (8) tau and the rho-hat slot count for a
    padded batch of probed candidates, with the heterogeneous worst-member
    device terms (per-server speed floors, shared/isolated uplinks with
    +inf where absent).

Each program has a plain ``jax.jit`` form and a Pallas form
(``use_kernel=True``: one grid step per :data:`ROW_BLOCK` rows, the row
reductions in VMEM; interpret mode on CPU, Mosaic on TPU).  Rows are
padded to power-of-two buckets, so the programs retrace only per (bucket,
cluster), never per job.

A call crosses the host-device boundary twice: one float32 operand
buffer goes in (:func:`_operand_buffer`: the ``[rows, width]`` block, then
one column per per-row or per-call value), and one int32 result buffer
comes out (:func:`_result_buffer`: integer columns, float32 blocks as
their bits).  The per-cluster constants stay on the device.  A call's
device work is a few microseconds, far less than one crossing costs, so
the number of crossings, not their bytes, sets a call's time.

**Precision contract: the same decisions as the float64 host oracle.**
The programs compute in int32/float32 (Mosaic has no 64-bit types, and
XLA's emulated f64 on TPU is not IEEE binary64).  Integer reductions --
pool counts, per-server feasible counts, the FA-FFP fit test -- are exact
given exact compares.  Every float that decides a placement is screened:
the program also returns, per row, whether any such float lies within its
f32 error bound of its threshold or its tie, and those rows are recomputed
in float64 on the host by the NumPy oracle expressions.  The bounds:

  * Eq. (16) pools: ``|V - T| <= POOL_REL * (|V| + |T|)``.  V = U + rho/u
    and T = theta + 1e-9 reach f32 through <= 3 roundings of non-negative
    terms (< 3 * 2^-24 relative); POOL_REL = 2^-20 leaves a 5x margin.
  * FA-FFP ``-load`` tie-break and the LBSGF ``load/cap`` ranking: a
    server's busy sum adds at most ``maxcap`` f32-rounded clocks (relative
    error < (maxcap + 2) * 2^-24 for non-negative terms, at
    ``Precision.HIGHEST``, where the TPU's matmul keeps f32 accuracy); the
    screen uses ``load_rel = (maxcap + 8) * 2^-20`` (16x) per operand.  Two
    loads that are both exactly zero are an exact tie: every nonzero clock
    is a sum of rho/u >= 1/u, far above f32's smallest normal, so f32 zero
    <=> float64 zero.
  * Eq. (8) ``phi = floor(1/tau)``: tau is a sum of four non-negative terms
    built from <= 9 rounded inputs and divisions (< 20 * 2^-24 relative
    through the reciprocal); SCORE_REL = 2^-17 (>6x) around each integer.
    ``ceil(iters / phi)`` is then made exact in int32 arithmetic.

Rows that pass every screen return the device's integers (and its f32
``load/cap`` keys, whose order then equals the float64 order); the rest
return the host recompute.  ``DISPATCH_COUNTS`` records both.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import spans

__all__ = ["pick_orders", "score_probes", "compile_counts",
           "DISPATCH_COUNTS", "min_dispatch_rows"]

#: Rows per kernel grid step (one TPU sublane tile of 32-bit values);
#: also the smallest padded row bucket.
ROW_BLOCK = 8

#: CPU only: below this many rows the stats run in NumPy instead of the
#: device program -- one CPU dispatch+fetch round-trip (~300us on the
#: host it was calibrated on) costs more than the reductions it replaces
#: (32-server Philly cluster at |J| = 8192: thresh 32 -> 37.6s, 64 ->
#: 32.9s vs 32.8s pure NumPy).  On an accelerator every batch dispatches;
#: ``use_kernel=True`` always dispatches.
DISPATCH_MIN_ROWS = 64

#: Screening bounds (see the module docstring).
POOL_REL = 2.0 ** -20
SCORE_REL = 2.0 ** -17
_PHI_MAX = 2.0 ** 23            # beyond this floor(1/tau) is not f32-exact

#: Calls that ran the device program / took the host NumPy path below the
#: CPU gate, rows dispatched, rows re-checked in float64 on the host, and
#: arrays that crossed the host-device boundary (either way).
DISPATCH_COUNTS = {"device": 0, "host": 0, "rows": 0, "rechecked": 0,
                   "transfers": 0}


def min_dispatch_rows() -> int:
    """Smallest batch that runs on the device (the CPU-only gate)."""
    return DISPATCH_MIN_ROWS if jax.default_backend() == "cpu" else 1


def _interpret(interpret: bool | None) -> bool:
    """Pallas interpret mode: explicit, else on CPU backends only."""
    return jax.default_backend() == "cpu" if interpret is None else interpret


def _bucket(n: int) -> int:
    """Power-of-two padding bucket for ``n`` rows (>= ROW_BLOCK)."""
    return max(ROW_BLOCK, 1 << (max(1, n) - 1).bit_length())


def _operand_buffer(R: int, block: np.ndarray, *cols) -> np.ndarray:
    """A device call's one float32 operand buffer ``[R, W + len(cols)]``.

    ``block`` ``[n, W]`` fills the first ``W`` columns, each of ``cols``
    (a length-``n`` array or a scalar) one column after it, and rows
    ``n..R`` are zero.  Every value is rounded to float32 once, as
    ``astype`` rounds it; the integers carried (GPU and occupancy
    counts, picker ids, ``ceil(lambda*G)``, iterations < 2^24) are
    exact."""
    n, W = block.shape
    buf = np.empty((R, W + len(cols)), dtype=np.float32)
    buf[:n, :W] = block
    for j, c in enumerate(cols, W):
        buf[:n, j] = c
    buf[n:] = 0.0
    return buf


def _result_buffer(*blocks):
    """A device call's one int32 result buffer: the ``[R, k]`` blocks side
    by side, integer and boolean blocks as int32 and float32 blocks as
    their bits (``bitcast_convert_type``), so every value comes back
    exactly."""
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(b, jnp.int32)
         if b.dtype == jnp.float32 else _i32(b) for b in blocks], axis=1)


def _round_trip(program, ops: np.ndarray, consts: tuple, rows: int,
                **static) -> np.ndarray:
    """One device call: ``program`` on the operand buffer ``ops`` and the
    device-resident ``consts``; returns the first ``rows`` rows of its
    result buffer on the host.

    The launch span holds the jit call (the operands' host-to-device
    copies and the enqueue), the fetch span the ``np.asarray`` (the wait
    for the program and the device-to-host copy).  ``DISPATCH_COUNTS
    ["transfers"]`` counts the host arrays sent and the buffer fetched."""
    with spans.span(spans.LAUNCH, rows=rows), jax.enable_x64(False):
        out = program(ops, *consts, **static)
    DISPATCH_COUNTS["transfers"] += sum(
        isinstance(a, np.ndarray) for a in (ops, *consts))
    with spans.span(spans.FETCH):
        out = np.asarray(out)[:rows]
    DISPATCH_COUNTS["transfers"] += 1
    DISPATCH_COUNTS["device"] += 1
    DISPATCH_COUNTS["rows"] += rows
    return out


@functools.lru_cache(maxsize=64)
def _cluster_consts(cluster) -> dict:
    """Per-cluster constant operands (cached; the Cluster dataclass is
    frozen/hashable).  ``member`` [N, S] is the 0/1 GPU-to-server
    membership the per-server sums multiply by."""
    caps = cluster.capacities_array
    S = cluster.num_servers
    member = (cluster.gpu_server[:, None] == np.arange(S)[None, :])
    f32 = np.float32
    return {
        "member": jnp.asarray(member.astype(f32)),
        "caps": jnp.asarray(caps.astype(f32)[None, :]),
        "load_rel": float((int(caps.max()) + 8) * 2.0 ** -20),
        "speed_floor": jnp.asarray(cluster.server_speed_floor.astype(f32)[None, :]),
        "uplink_shared": jnp.asarray(cluster.uplink_shared_or_inf.astype(f32)[None, :]),
        "uplink_isolated": jnp.asarray(cluster.uplink_isolated_or_inf.astype(f32)[None, :]),
        # Host copies for the NumPy ranking half.
        "np_gpu_server": np.asarray(cluster.gpu_server),
        "np_caps": np.asarray(caps),
    }


# --------------------------------------------------------------------------
# Row math (shared verbatim by the jnp programs and the Pallas kernels)
# --------------------------------------------------------------------------


def _i32(x):
    return x.astype(jnp.int32)


def _near(a, b, rel):
    """``a`` within the relative screening bound of ``b``."""
    return jnp.abs(a - b) <= rel * (jnp.abs(a) + jnp.abs(b))


def _pool_row_math(U, t_lo, t_hi, rho_u, G, member, *, load_rel):
    """Pools, thresholds and per-server reductions for a ``[R, N]`` block.

    ``t_lo``/``t_hi``/``rho_u``/``G`` are ``[R, 1]`` columns (thresholds
    already include the +1e-9).  Returns ``[R, 1]`` int32 columns
    ``(c_lo, c_hi, best_srv, has_fit, pool_unsure, fa_unsure)`` and the
    ``[R, S]`` float32 per-server busy sums."""
    N = U.shape[-1]
    S = member.shape[-1]
    V = U + rho_u
    feas = V <= t_lo                                    # Eq. (16) pool
    c_lo = jnp.sum(_i32(feas), axis=-1, keepdims=True)
    c_hi = jnp.sum(_i32(V <= t_hi), axis=-1, keepdims=True)
    pool_unsure = jnp.max(
        _i32(_near(V, t_lo, POOL_REL) | _near(V, t_hi, POOL_REL)),
        axis=-1, keepdims=True)
    hi = jax.lax.Precision.HIGHEST
    load = jnp.dot(U, member, precision=hi,
                   preferred_element_type=jnp.float32)
    # 0/1 products summed in f32: exact integer counts at any precision.
    cnt = _i32(jnp.dot(feas.astype(jnp.float32), member, precision=hi,
                       preferred_element_type=jnp.float32))
    # FA-FFP best server: lexicographic min over (feasible slots left,
    # -occupancy, server id) as staged masked reductions -- ties resolved
    # by first index, as the host lexsort does.
    fits = cnt >= G
    has_fit = jnp.max(_i32(fits), axis=-1, keepdims=True)
    k_fit = jnp.where(fits, cnt - G, N + 1)
    t1 = k_fit == jnp.min(k_fit, axis=-1, keepdims=True)
    k2 = jnp.where(t1, load, -1.0)
    lmax = jnp.max(k2, axis=-1, keepdims=True)
    sid = jax.lax.broadcasted_iota(jnp.int32, load.shape, 1)
    best = jnp.min(jnp.where(t1 & (k2 == lmax), sid, S), axis=-1,
                   keepdims=True)
    # Another tied-fit server within the load bound of the winner.
    tie = t1 & (lmax - load <= load_rel * (lmax + load))
    fa_unsure = _i32((jnp.sum(_i32(tie), axis=-1, keepdims=True) > 1)
                     & (lmax > 0) & (has_fit > 0))
    return c_lo, c_hi, load, best, has_fit, pool_unsure, fa_unsure


def _score_row_math(Y, f, gamma, scal, speed_floor, uplink_sh, uplink_iso,
                    *, hetero, b_inter, b_intra):
    """Eq. (6)-(8) tau -> rho-hat slots for a ``[R, S]`` candidate block.

    ``Y`` holds the occupancy counts (only ``Y > 0`` is read),
    ``f``/``gamma`` are ``[R, 1]`` host-computed contention terms, ``scal``
    the ``[R, 5]`` job columns (two_share, share, reduce_const, compute,
    iters) and the device terms ``[1, S]``.  Returns int32 ``[R, 1]`` columns
    ``(rho, unsure)``: rho = ceil(iters / max(1, floor(1/tau))) exactly
    whenever ``unsure`` is 0."""
    two_share, share = scal[:, 0:1], scal[:, 1:2]
    reduce_const, compute, iters = scal[:, 2:3], scal[:, 3:4], scal[:, 4:5]
    pos = Y > 0
    multi = jnp.sum(_i32(pos), axis=-1, keepdims=True) > 1
    if hetero:
        inf = jnp.float32(jnp.inf)
        speed = jnp.min(jnp.where(pos, speed_floor, inf), axis=-1,
                        keepdims=True)
        bw_sh = jnp.min(jnp.where(pos, uplink_sh, inf), axis=-1,
                        keepdims=True)
        bw_iso = jnp.min(jnp.where(pos, uplink_iso, inf), axis=-1,
                         keepdims=True)
        bw_multi = jnp.minimum(bw_iso, bw_sh / f)
        reduce_t = share / speed
    else:
        bw_multi = b_inter / f
        reduce_t = reduce_const
    bandwidth = jnp.where(multi, bw_multi, b_intra)
    tau = two_share / bandwidth + reduce_t + gamma + compute   # Eq. (8)
    inv = 1.0 / tau
    k = jnp.floor(inv + 0.5)
    unsure = ((k >= 1.0) & (jnp.abs(inv - k) <= SCORE_REL * inv)) \
        | (inv >= _PHI_MAX)
    phi = _i32(jnp.maximum(1.0, jnp.floor(jnp.minimum(inv, _PHI_MAX))))
    # ceil(iters / phi), corrected to the exact integer quotient.
    it = _i32(iters)
    rho = _i32(jnp.ceil(iters / phi.astype(jnp.float32)))
    rho = jnp.where(rho * phi < it, rho + 1, rho)
    rho = jnp.where((rho - 1) * phi >= it, rho - 1, rho)
    return rho, _i32(unsure)


# --------------------------------------------------------------------------
# Pallas kernel bodies (one grid step per ROW_BLOCK rows, VMEM reductions)
# --------------------------------------------------------------------------


def _pool_kernel(U_ref, tlo_ref, thi_ref, ru_ref, g_ref, m_ref, clo_ref,
                 chi_ref, load_ref, best_ref, fit_ref, pu_ref, fu_ref, *,
                 load_rel):
    """ROW_BLOCK branch rows: Eq. (16) pools + per-server reductions."""
    c_lo, c_hi, load, best, fit, pu, fu = _pool_row_math(
        U_ref[...], tlo_ref[...], thi_ref[...], ru_ref[...], g_ref[...],
        m_ref[...], load_rel=load_rel)
    clo_ref[...] = c_lo
    chi_ref[...] = c_hi
    load_ref[...] = load
    best_ref[...] = best
    fit_ref[...] = fit
    pu_ref[...] = pu
    fu_ref[...] = fu


def _score_kernel(Y_ref, f_ref, gamma_ref, scal_ref, spd_ref, sh_ref,
                  iso_ref, rho_ref, un_ref, *, hetero, b_inter, b_intra):
    """ROW_BLOCK candidate rows: Eq. (6)-(8) tau -> rho-hat slots."""
    rho, unsure = _score_row_math(
        Y_ref[...], f_ref[...], gamma_ref[...], scal_ref[...], spd_ref[...],
        sh_ref[...], iso_ref[...], hetero=hetero, b_inter=b_inter,
        b_intra=b_intra)
    rho_ref[...] = rho
    un_ref[...] = unsure


def _row_spec(width):
    return pl.BlockSpec((ROW_BLOCK, width), lambda b: (b, 0))


def _whole_spec(shape):
    return pl.BlockSpec(shape, lambda b: (0, 0))


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


# --------------------------------------------------------------------------
# Fused jit programs
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("load_rel", "use_kernel",
                                             "interpret"))
def _pool_stats_jit(ops, member, caps, *, load_rel, use_kernel, interpret):
    """One program: pools, thresholds, per-server reductions, screens.

    ``ops`` is the ``[B, N + 6]`` operand buffer: the busy-time rows
    ``U``, then the columns ``t_lo``, ``t_hi`` (thresholds with the
    +1e-9), ``rho_u``, ``G``, ``pid`` and ``need`` (``ceil(lambda*G)``).
    Returns the int32 ``[B, S + 5]`` result buffer: ``c_lo``, ``c_hi``,
    ``best_srv``, ``has_fit``, ``unsure``, then the bits of the float32
    LBSGF ``load/cap`` ranking keys.  ``unsure`` flags rows whose
    decisions the float64 host must recompute (pool thresholds; the
    FA-FFP tie-break for picker 0, the LBSGF ranking for picker 1)."""
    B = ops.shape[0]
    N, S = member.shape
    U = ops[:, :N]
    t_lo, t_hi, rho_u, G, pid, need = (ops[:, j:j + 1]
                                       for j in range(N, N + 6))
    G = _i32(G)
    if use_kernel:
        col = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        outs = pl.pallas_call(
            functools.partial(_pool_kernel, load_rel=load_rel),
            grid=(B // ROW_BLOCK,),
            in_specs=[_row_spec(N), _row_spec(1), _row_spec(1),
                      _row_spec(1), _row_spec(1), _whole_spec((N, S))],
            out_specs=[_row_spec(1), _row_spec(1), _row_spec(S),
                       _row_spec(1), _row_spec(1), _row_spec(1),
                       _row_spec(1)],
            out_shape=[col, col, jax.ShapeDtypeStruct((B, S), jnp.float32),
                       col, col, col, col],
            compiler_params=_PARALLEL,
            interpret=interpret,
        )(U, t_lo, t_hi, rho_u, G, member)
    else:
        outs = _pool_row_math(U, t_lo, t_hi, rho_u, G, member,
                              load_rel=load_rel)
    c_lo, c_hi, load, best, fit, pool_u, fa_u = outs
    key = load / caps
    # LBSGF screen.  The picker uses only the least-loaded prefix of
    # servers whose capacity before them is < lambda*G (``need``), in
    # stable key order; it is the float64 one when no prefix server's
    # key lies within the summed bounds of any other server's (an exact
    # tie of two zero loads is exact in both precisions).
    a, b = key[:, :, None], key[:, None, :]
    sid = jnp.arange(S)
    before = (b < a) | ((b == a) & (sid[None, :] < sid[:, None])[None])
    in_prefix = jnp.sum(jnp.where(before, caps[:, None, :], 0.0),
                        axis=2) < need
    close = (jnp.abs(a - b) <= load_rel * (a + b)) & ~((a == 0) & (b == 0))
    close = close & (sid[:, None] != sid[None, :])[None]
    lb_u = jnp.any(close & (in_prefix[:, :, None] | in_prefix[:, None, :]),
                   axis=(1, 2))
    unsure = (pool_u > 0) | jnp.where(pid == 0, fa_u > 0, lb_u[:, None])
    return _result_buffer(c_lo, c_hi, best, fit, unsure, key)


@functools.partial(jax.jit, static_argnames=(
    "hetero", "b_inter", "b_intra", "use_kernel", "interpret"))
def _score_probes_jit(ops, speed_floor, uplink_sh, uplink_iso, *, hetero,
                      b_inter, b_intra, use_kernel, interpret):
    """One program: Eq. (6)-(8) rho-hat slots + screen for a candidate
    batch.  ``ops`` is the ``[B, S + 7]`` operand buffer: the occupancy
    rows ``Y``, then the columns ``f``, ``gamma`` (host-computed, see
    :func:`score_probes`) and the job's five ``scal`` terms.  Returns the
    int32 ``[B, 2]`` result buffer ``(rho, unsure)``."""
    B = ops.shape[0]
    S = speed_floor.shape[1]
    Y, f, gamma, scal = (ops[:, :S], ops[:, S:S + 1], ops[:, S + 1:S + 2],
                         ops[:, S + 2:])
    kw = dict(hetero=hetero, b_inter=b_inter, b_intra=b_intra)
    if not use_kernel:
        rho, unsure = _score_row_math(Y, f, gamma, scal, speed_floor,
                                      uplink_sh, uplink_iso, **kw)
    else:
        col = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        rho, unsure = pl.pallas_call(
            functools.partial(_score_kernel, **kw),
            grid=(B // ROW_BLOCK,),
            in_specs=[_row_spec(S), _row_spec(1), _row_spec(1),
                      _row_spec(5), _whole_spec((1, S)),
                      _whole_spec((1, S)), _whole_spec((1, S))],
            out_specs=[_row_spec(1), _row_spec(1)],
            out_shape=[col, col],
            compiler_params=_PARALLEL,
            interpret=interpret,
        )(Y, f, gamma, scal, speed_floor, uplink_sh, uplink_iso)
    return _result_buffer(rho, unsure)


# --------------------------------------------------------------------------
# Host halves (the float64 oracle expressions)
# --------------------------------------------------------------------------


def _pool_stats_host(cluster, U, V, feas, th_hi, G):
    """float64 ``(c_lo, c_hi, key, best_srv, has_fit)`` -- the NumPy
    pickers' reductions, in ``np.bincount``'s GPU-id addition order."""
    from repro.core.columnar import server_sums
    N = U.shape[1]
    c_lo = feas.sum(axis=1)
    c_hi = (V <= th_hi[:, None] + 1e-9).sum(axis=1)
    load = server_sums(cluster, U)
    cnt = server_sums(cluster, feas.astype(np.float64)).astype(np.int64)
    fits = cnt >= G
    has_fit = fits.any(axis=1)
    k_fit = np.where(fits, cnt - G, N + 1)
    k_occ = np.where(fits, -load, np.inf)
    t1 = k_fit == k_fit.min(axis=1, keepdims=True)
    k2 = np.where(t1, k_occ, np.inf)
    t2 = t1 & (k2 == k2.min(axis=1, keepdims=True))
    key = load / cluster.capacities_array[None, :].astype(np.float64)
    return c_lo, c_hi, key, t2.argmax(axis=1), has_fit


def _score_host(cluster, job, Y, p):
    """float64 rho-hat slots (``scalar_tau_many`` + ``slots_for_many``)."""
    from repro.core import contention as ct
    n_srv = (Y > 0).sum(axis=1)
    if cluster.is_heterogeneous:
        tau = ct.scalar_tau_many(cluster, job, p, n_srv,
                                 *ct._hetero_mins(cluster, Y > 0))
    else:
        tau = ct.scalar_tau_many(cluster, job, p, n_srv)
    return ct.slots_for_many(job.iters, tau)


# --------------------------------------------------------------------------
# Public entry points (NumPy in, NumPy out, power-of-two padding)
# --------------------------------------------------------------------------


@spans.spanned(spans.PICK_ORDERS)
def pick_orders(cluster, U_stack: np.ndarray, th_lo: np.ndarray,
                th_hi: np.ndarray, rho_u: np.ndarray, pid: np.ndarray,
                job, *, use_kernel: bool = False,
                interpret: bool | None = None):
    """Fused pool/threshold/pick program over one step's work items.

    ``U_stack`` [nw, N] gathers each work item's busy-time row; ``th_lo``/
    ``th_hi`` its extreme branch thetas, ``rho_u`` its escalated rho/u
    charge and ``pid`` its picker id (0 = FA-FFP, 1 = LBSGF).  Returns
    NumPy ``(V, c_lo, c_hi, order, ok)``: the charged clocks, pool counts
    at both extremes, each row's full stable GPU ordering (the pick is
    ``order[i, :G_j]``) and the pool-large-enough flag -- equal to the
    NumPy ``pick_many`` forms on every row (screened rows are recomputed
    on the host; see the module docstring).

    The device program computes the reductions; the stable rankings run
    here with NumPy's sorts, mirroring the second halves of
    ``_fa_ffp_many`` / ``_lbsgf_many`` term for term.  On CPU, batches
    under :data:`DISPATCH_MIN_ROWS` skip the device round-trip.
    """
    nw = U_stack.shape[0]
    G = job.num_gpus
    consts = _cluster_consts(cluster)
    V = U_stack + rho_u[:, None]
    feas = V <= th_lo[:, None] + 1e-9                  # Eq. (16) pool
    if use_kernel or nw >= min_dispatch_rows():
        ops = _operand_buffer(
            _bucket(nw), U_stack, th_lo + 1e-9, th_hi + 1e-9, rho_u, G, pid,
            # cum < lambda*G over integer capacities == cum < ceil(.)
            math.ceil(job.lam * G))
        out = _round_trip(
            _pool_stats_jit, ops, (consts["member"], consts["caps"]), nw,
            load_rel=consts["load_rel"], use_kernel=use_kernel,
            interpret=_interpret(interpret))
        c_lo, c_hi, best_srv, has_fit, unsure = out[:, :5].T.astype(np.int64)
        has_fit = has_fit.astype(bool)
        key = out[:, 5:].view(np.float32).astype(np.float64)
        redo = np.flatnonzero(unsure)
        if redo.size:
            DISPATCH_COUNTS["rechecked"] += redo.size
            with spans.span(spans.RECHECK):
                (c_lo[redo], c_hi[redo], key[redo], best_srv[redo],
                 has_fit[redo]) = _pool_stats_host(
                    cluster, U_stack[redo], V[redo], feas[redo], th_hi[redo],
                    G)
    else:
        DISPATCH_COUNTS["host"] += 1
        c_lo, c_hi, key, best_srv, has_fit = _pool_stats_host(
            cluster, U_stack, V, feas, th_hi, G)
    with spans.span(spans.RANK):
        order, ok = _rank(U_stack, feas, pid, c_lo, key, best_srv, has_fit,
                          consts, job)
    return V, c_lo, c_hi, order, ok


def _rank(U, feas, pid, c_lo, key, best_srv, has_fit, consts, job):
    """:func:`pick_orders`' stable GPU orderings and pool-large-enough
    flags, from the program's reductions."""
    nw, N = U.shape
    G = job.num_gpus
    gpu_server = consts["np_gpu_server"]
    caps = consts["np_caps"]
    S = caps.shape[0]
    order = np.empty((nw, N), dtype=np.int64)
    ok = np.empty(nw, dtype=bool)
    fa = np.flatnonzero(pid == 0)
    if fa.size:
        # FA-FFP: pack into the best-fit server when one fits, else
        # spread over the whole pool (== _fa_ffp_many's masked keys).
        in_best = feas[fa] & (gpu_server[None, :] == best_srv[fa, None])
        keys = np.where(has_fit[fa, None],
                        np.where(in_best, U[fa], np.inf),
                        np.where(feas[fa], U[fa], np.inf))
        order[fa] = np.argsort(keys, axis=1, kind="stable")
        ok[fa] = c_lo[fa] >= G
    lb = np.flatnonzero(pid == 1)
    if lb.size:
        # LBSGF: least-busy server prefix of lambda_j*G capacity, then
        # server-rank-major / least-U lexsort (== _lbsgf_many).
        nl = lb.size
        srv_order = np.argsort(key[lb], axis=1, kind="stable")
        cum = np.cumsum(np.take_along_axis(
            np.broadcast_to(caps[None, :], srv_order.shape), srv_order,
            axis=1), axis=1)
        m = np.minimum((cum < job.lam * G).sum(axis=1) + 1, S)
        pos = np.arange(S)[None, :]
        rank_vals = np.where(pos < m[:, None], pos, -1)
        srv_rank = np.empty_like(srv_order)
        np.put_along_axis(srv_rank, srv_order, rank_vals, axis=1)
        ranks = srv_rank[:, gpu_server]
        pool = feas[lb] & (ranks >= 0)
        ok[lb] = pool.sum(axis=1) >= G
        k_rank = np.where(pool, ranks, S + 1)
        k_U = np.where(pool, U[lb], np.inf)
        r_off = (np.arange(nl) * N)[:, None]
        flat = np.lexsort((k_U.ravel(), k_rank.ravel(),
                           np.repeat(np.arange(nl), N)))
        order[lb] = flat.reshape(nl, N) - r_off
    return order, ok


@spans.spanned(spans.SCORE_PROBES)
def score_probes(cluster, job, Y: np.ndarray, p: np.ndarray, *,
                 use_kernel: bool = False, interpret: bool | None = None
                 ) -> np.ndarray:
    """Fused Eq. (6)-(8) rho-hat slot counts of one step's probed
    candidates.

    ``Y`` [C, S] holds each candidate's occupancy row and ``p`` its
    host-probed contention level.  Returns float64 ``rho`` [C] equal to
    ``slots_for_many(job.iters, scalar_tau_many(...))`` on every row
    (screened rows are recomputed on the host); heterogeneous clusters
    price worst-member device terms like
    :func:`repro.core.contention._hetero_mins`.  On CPU, batches under
    :data:`DISPATCH_MIN_ROWS` score through the NumPy forms directly.
    """
    C, S = Y.shape
    p = np.asarray(p, dtype=np.float64)
    if not use_kernel and C < min_dispatch_rows():
        DISPATCH_COUNTS["host"] += 1
        return _score_host(cluster, job, Y, p)
    from repro.core.contention import degradation
    if not job.iters < 2 ** 24:
        raise ValueError(f"iters={job.iters} is not exact in float32")
    # The contention terms that take integer p/n_srv in (k, f, gamma) are
    # evaluated on the host in float64 and rounded once.
    f = degradation(cluster.alpha, np.maximum(cluster.xi1 * p, 1.0))
    gamma = cluster.xi2 * (Y > 0).sum(axis=1).astype(np.float64)
    w = float(job.num_gpus)
    share = (job.grad_size / w) * (w - 1.0) if w > 1 else 0.0
    compute = job.dt_fwd * float(job.batch) + job.dt_bwd
    consts = _cluster_consts(cluster)
    ops = _operand_buffer(_bucket(C), Y, f, gamma, 2.0 * share, share,
                          share / cluster.gpu_speed, compute,
                          float(job.iters))
    out = _round_trip(
        _score_probes_jit, ops, (consts["speed_floor"],
                                 consts["uplink_shared"],
                                 consts["uplink_isolated"]), C,
        hetero=cluster.is_heterogeneous, b_inter=float(cluster.b_inter),
        b_intra=float(cluster.b_intra), use_kernel=use_kernel,
        interpret=_interpret(interpret))
    rho = out[:, 0].astype(np.float64)
    redo = np.flatnonzero(out[:, 1])
    if redo.size:
        DISPATCH_COUNTS["rechecked"] += redo.size
        with spans.span(spans.RECHECK):
            rho[redo] = _score_host(cluster, job, Y[redo], p[redo])
    return rho


def compile_counts() -> dict[str, int]:
    """Compiled-variant counts of the fused programs (the no-retrace
    guard: bounded by padding buckets x clusters, never growing per job)."""
    return {"pick_orders": _pool_stats_jit._cache_size(),
            "score_probes": _score_probes_jit._cache_size()}

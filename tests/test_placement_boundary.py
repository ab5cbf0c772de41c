"""The placement programs' host-device boundary: one operand buffer goes
in and one result buffer comes out per device call, float32 keys cross
bit for bit inside the int32 result buffer, and the padded rows of a
bucket never reach what the host gets back."""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import repro.kernels.placement as kp  # noqa: E402
from repro.core import Cluster, Job  # noqa: E402

CLUSTER = Cluster((8, 4, 8, 16))
HETERO = dataclasses.replace(
    CLUSTER, gpu_speeds=tuple([CLUSTER.gpu_speed] * 20
                              + [CLUSTER.gpu_speed / 2] * 16),
    links=tuple((CLUSTER.b_inter, kind) for kind in ("shared", "isolated")
                * 2))
JOB = Job(jid=0, num_gpus=4, iters=1000, grad_size=1e-3, batch=32,
          dt_fwd=3e-4, dt_bwd=8e-3)


def _pick(rng, n, **kw):
    N = CLUSTER.num_gpus
    U = np.round(rng.uniform(0, 30, size=(n, N)), 3)
    th_lo = rng.uniform(5, 40, size=n)
    return kp.pick_orders(CLUSTER, U, th_lo, th_lo + rng.uniform(0, 10, n),
                          rng.uniform(0.5, 20, n), rng.integers(0, 2, n),
                          JOB, **kw)


def _score(rng, n, **kw):
    Y = rng.integers(0, 3, size=(n, HETERO.num_servers))
    p = rng.integers(1, 4, size=n).astype(np.float64)
    return (kp.score_probes(HETERO, JOB, Y, p, **kw),)


PROGRAMS = {"pick_orders": _pick, "score_probes": _score}


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_one_transfer_each_way(program, use_kernel, monkeypatch):
    monkeypatch.setattr(kp, "DISPATCH_MIN_ROWS", 0)
    rng = np.random.default_rng(0)
    before = dict(kp.DISPATCH_COUNTS)
    for n in (1, 5, 8, 13):
        PROGRAMS[program](rng, n, use_kernel=use_kernel)
    d = {k: kp.DISPATCH_COUNTS[k] - before[k] for k in before}
    assert d["device"] == 4 and d["host"] == 0
    assert d["transfers"] == 2 * d["device"]


def test_result_buffer_carries_float_bits():
    keys = np.array([[0.0, -0.0, 1e-45, 1.5e-38, 1 / 3, 7.25, 3.4e38,
                      np.inf]], dtype=np.float32).repeat(3, axis=0)
    keys[1] = np.roll(keys[1], 3)
    counts = np.array([[7], [0], [-3]], dtype=np.int32)
    flags = np.array([[True], [False], [True]])
    with jax.enable_x64(False):
        out = np.asarray(jax.jit(kp._result_buffer)(
            jnp.asarray(counts), jnp.asarray(flags), jnp.asarray(keys)))
    assert out.dtype == np.int32 and out.shape == (3, 2 + keys.shape[1])
    assert np.array_equal(out[:, :2], np.hstack([counts, flags]))
    assert np.array_equal(out[:, 2:], keys.view(np.int32))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_keys_cross_bit_for_bit(use_kernel):
    """Busy times in quarters sum exactly in float32 in any order, so each
    ``load/cap`` key is one rounded division, as in NumPy; an idle server
    in every row and four idle rows give keys of exactly zero."""
    rng = np.random.default_rng(3)
    n, S = 16, CLUSTER.num_servers
    srv = CLUSTER.gpu_server
    U = rng.integers(0, 400, size=(n, CLUSTER.num_gpus)) / 4.0
    U[:, srv == 1] = 0.0
    U[:4] = 0.0
    consts = kp._cluster_consts(CLUSTER)
    ops = kp._operand_buffer(n, U, 1e6, 1e6, 1.0, JOB.num_gpus, 1, 4)
    with jax.enable_x64(False):
        out = np.asarray(kp._pool_stats_jit(
            ops, consts["member"], consts["caps"],
            load_rel=consts["load_rel"], use_kernel=use_kernel,
            interpret=kp._interpret(None)))
    assert out.dtype == np.int32 and out.shape == (n, S + 5)
    load = np.stack([U[:, srv == s].sum(axis=1) for s in range(S)], axis=1)
    want = load.astype(np.float32) / \
        CLUSTER.capacities_array.astype(np.float32)
    assert np.array_equal(out[:, 5:], want.view(np.int32))
    assert (want == 0).sum() == 4 * S + (n - 4)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_padded_rows_stay_on_the_device(program, use_kernel, monkeypatch):
    """Rows n..R of each bucket hold NaN here, so the program's answers
    there are garbage: the host still gets back n rows, each equal to
    the NumPy path's."""
    real_buffer, real_trip = kp._operand_buffer, kp._round_trip
    buckets = []

    def nan_padded(R, block, *cols):
        buf = real_buffer(R, block, *cols)
        buf[block.shape[0]:] = np.nan
        return buf

    def trip(prog, ops, consts, rows, **static):
        buckets.append((ops.shape[0], rows))
        return real_trip(prog, ops, consts, rows, **static)

    monkeypatch.setattr(kp, "_operand_buffer", nan_padded)
    monkeypatch.setattr(kp, "_round_trip", trip)
    for n in (3, 5, 13):
        monkeypatch.setattr(kp, "DISPATCH_MIN_ROWS", 10**9)
        ref = PROGRAMS[program](np.random.default_rng(n), n)
        monkeypatch.setattr(kp, "DISPATCH_MIN_ROWS", 0)
        dev = PROGRAMS[program](np.random.default_rng(n), n,
                                use_kernel=use_kernel)
        for a, b in zip(ref, dev):
            assert np.asarray(b).shape[0] == n
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert buckets == [(8, 3), (8, 5), (16, 13)]

"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle.

Sweeps shapes and dtypes per the deliverable spec and asserts allclose
against ``repro.kernels.ref``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# These run in Pallas interpret mode on CPU (the kernels default to
# interpret=True off-accelerator), so no `gpu` marker: CI runs them.

from repro.kernels import ref
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention as fa_kernel
from repro.kernels.rmsnorm import rmsnorm as rn_kernel
from repro.kernels.swiglu import swiglu as sg_kernel


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _assert_close(a, b, dtype):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), **_tol(dtype))


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,S,hd", [
        (1, 1, 128, 64), (2, 4, 256, 64), (1, 2, 512, 128), (2, 1, 128, 256),
    ])
    def test_causal_matches_ref(self, B, H, S, hd, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
        k = jax.random.normal(ks[1], (B, H, S, hd), dtype)
        v = jax.random.normal(ks[2], (B, H, S, hd), dtype)
        out = fa_kernel(q, k, v, causal=True, block_q=128, block_k=128)
        exp = ref.flash_attention(q, k, v, causal=True)
        _assert_close(out, exp, dtype)

    @pytest.mark.parametrize("window", [32, 128, 300])
    def test_sliding_window(self, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (jax.random.normal(ks[i], (1, 2, 256, 64), jnp.float32)
                   for i in range(3))
        out = fa_kernel(q, k, v, causal=True, window=window,
                        block_q=64, block_k=64)
        exp = ref.flash_attention(q, k, v, causal=True, window=window)
        _assert_close(out, exp, jnp.float32)

    def test_softcap(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q, k, v = (jax.random.normal(ks[i], (1, 2, 128, 64), jnp.float32) * 3
                   for i in range(3))
        out = fa_kernel(q, k, v, causal=True, softcap=50.0,
                        block_q=64, block_k=64)
        exp = ref.flash_attention(q, k, v, causal=True, softcap=50.0)
        _assert_close(out, exp, jnp.float32)

    def test_non_causal(self):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (2, 2, 128, 64), jnp.float32)
        k = jax.random.normal(ks[1], (2, 2, 256, 64), jnp.float32)
        v = jax.random.normal(ks[2], (2, 2, 256, 64), jnp.float32)
        out = fa_kernel(q, k, v, causal=False, block_q=64, block_k=64)
        exp = ref.flash_attention(q, k, v, causal=False)
        _assert_close(out, exp, jnp.float32)

    def test_ops_wrapper_gqa_and_padding(self):
        """Model layout [B,S,H,hd], GQA repeat, non-multiple seq lens."""
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        B, S, H, K, hd = 2, 200, 8, 2, 64
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True)
        kk = jnp.repeat(k, H // K, axis=2).transpose(0, 2, 1, 3)
        vv = jnp.repeat(v, H // K, axis=2).transpose(0, 2, 1, 3)
        exp = ref.flash_attention(q.transpose(0, 2, 1, 3), kk, vv,
                                  causal=True).transpose(0, 2, 1, 3)
        _assert_close(out, exp, jnp.float32)


class TestRMSNorm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("rows,d", [(8, 128), (256, 512), (1024, 4096),
                                        (64, 3584)])
    def test_matches_ref(self, rows, d, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(ks[0], (rows, d), dtype)
        s = jax.random.normal(ks[1], (d,), dtype) + 1.0
        out = rn_kernel(x, s, block_rows=min(256, rows))
        _assert_close(out, ref.rmsnorm(x, s), dtype)

    def test_ops_wrapper_nd(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 64, 128))
        s = jnp.ones((128,))
        _assert_close(ops.rmsnorm(x, s), ref.rmsnorm(
            x.reshape(-1, 128), s).reshape(x.shape), jnp.float32)


class TestSwiGLU:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("M,K,N", [(128, 512, 128), (256, 1024, 512),
                                       (128, 256, 384)])
    def test_matches_ref(self, M, K, N, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(ks[0], (M, K), dtype) * 0.1
        wg = jax.random.normal(ks[1], (K, N), dtype) * 0.05
        wu = jax.random.normal(ks[2], (K, N), dtype) * 0.05
        out = sg_kernel(x, wg, wu, block_m=128, block_n=128,
                        block_k=min(512, K))
        _assert_close(out, ref.swiglu(x, wg, wu), dtype)

    def test_ops_wrapper_batched(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 256)) * 0.1
        wg = jax.random.normal(jax.random.PRNGKey(2), (256, 128)) * 0.05
        wu = jax.random.normal(jax.random.PRNGKey(3), (256, 128)) * 0.05
        out = ops.swiglu(x, wg, wu)
        exp = ref.swiglu(x.reshape(-1, 256), wg, wu).reshape(2, 64, 128)
        _assert_close(out, exp, jnp.float32)


class TestKernelVsModelLayer:
    """The kernels must agree with the model's in-line reference math."""

    def test_flash_equals_model_sdpa(self):
        from repro.models.layers import _sdpa
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        B, S, H, hd = 2, 128, 4, 64
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        exp = _sdpa(q, k, v, pos, pos, causal=True, window=0, softcap=0.0,
                    compute_dtype=jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True)
        _assert_close(out.reshape(B, S, H * hd), exp, jnp.float32)


class TestTauKernel:
    """The Eq. (6)-(8) stack kernel vs the NumPy contention engines."""

    def _case(self, seed=0, n_cands=6):
        from repro.core import philly_cluster, philly_workload
        rng = np.random.default_rng(seed)
        cluster = philly_cluster(6, seed=seed)
        jobs = philly_workload(seed=seed, mix=((1, 4), (2, 4), (4, 4),
                                               (8, 2)))
        S = cluster.num_servers
        stack = np.zeros((n_cands, len(jobs), S), dtype=np.int64)
        for c in range(n_cands):
            for i, job in enumerate(jobs):
                for _ in range(job.num_gpus):
                    stack[c, i, rng.integers(S)] += 1
        return cluster, jobs, stack

    def test_tau_stack_matches_numpy_f32(self):
        """The 32-bit kernel's Eq. (6) counts are exact integers."""
        from repro.core.contention import _job_terms, evaluate_many
        from repro.kernels.tau import stack_counts
        cluster, jobs, stack = self._case()
        ref_model = evaluate_many(cluster, jobs, stack)
        G, _, _ = _job_terms(jobs)
        p, n_srv = stack_counts(G, stack)
        assert np.array_equal(p, ref_model.p)       # integer: exact
        assert np.array_equal(n_srv, (stack > 0).sum(axis=2))
        # Per-candidate [C, J] terms (the columnar branch-stack layout).
        p2, n2 = stack_counts(np.broadcast_to(G, stack.shape[:2]), stack)
        assert np.array_equal(p2, p) and np.array_equal(n2, n_srv)

    def test_tau_backend_bit_identity_x64(self):
        """The kernel path of stack_model / evaluate_many is bit-identical
        to the NumPy engines: exact int32 counts on the device, Eq. (8)
        in float64 on the host -- with x64 off, as the kernels run."""
        from repro.core.contention import evaluate, evaluate_many, tau_backend
        cluster, jobs, stack = self._case(seed=3)
        ref_model = evaluate_many(cluster, jobs, stack)
        with tau_backend("kernel"):
            kern = evaluate_many(cluster, jobs, stack)
        assert np.array_equal(ref_model.p, kern.p)
        assert np.array_equal(ref_model.tau, kern.tau)
        assert np.array_equal(ref_model.phi, kern.phi)
        assert np.array_equal(ref_model.bandwidth, kern.bandwidth)
        for c in range(stack.shape[0]):
            per = evaluate(cluster, jobs, stack[c])
            assert np.array_equal(per.tau, kern.tau[c])

    def test_unknown_tau_backend_rejected(self):
        from repro.core.contention import tau_backend
        with pytest.raises(ValueError, match="tau backend"):
            with tau_backend("cuda"):
                pass

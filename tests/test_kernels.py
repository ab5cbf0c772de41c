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


def _rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


def _scaled_blocks(S):
    """The dispatcher's block rule with its caps scaled from 4,096
    positions down to ``S`` (to a power of two, 128 at least), so that a
    short sequence still spans several blocks and masked ones are
    skipped."""
    def scaled(b):
        return max(128, 1 << (b * S // 4096).bit_length() - 1)

    return ops.causal_blocks(S, *(tuple(map(scaled, pair))
                                  for pair in (ops.FWD_BLOCKS, ops.DKV_BLOCKS,
                                               ops.DQ_BLOCKS)))


class TestCausalFlashTraining:
    """The training path's fused causal attention (JAX's bundled Pallas
    kernel, in TPU interpret mode) against the model's own ``_sdpa``:
    output and the gradients of q, k and v, at InternVL2's GQA 14/2 and
    head_dim 64."""

    @pytest.mark.parametrize("S,dtype,tol", [
        (512, jnp.float32, 1e-5), (512, jnp.bfloat16, 2e-2),
        (640, jnp.float32, 1e-5)])               # 640: blocks cut to 128
    def test_matches_sdpa_with_grads(self, S, dtype, tol):
        from jax.experimental.pallas import tpu as pltpu

        from repro.models.layers import _sdpa
        B, H, K, hd = 1, 14, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(6), 4)
        q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
        k = jax.random.normal(ks[1], (B, S, K, hd), dtype)
        v = jax.random.normal(ks[2], (B, S, K, hd), dtype)
        g = jax.random.normal(ks[3], (B, S, H * hd), dtype)
        pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        blocks = _scaled_blocks(S)

        def fused(q, k, v):
            return ops.causal_flash_attention(
                q, k, v, blocks=blocks).reshape(B, S, H * hd)

        def plain(q, k, v):
            return _sdpa(q, k, v, pos, pos, causal=True, window=0,
                         softcap=0.0, compute_dtype=dtype)

        with pltpu.force_tpu_interpret_mode():
            out, vjp = jax.vjp(jax.jit(fused), q, k, v)
            grads = vjp(g)
        exp, vjp_exp = jax.vjp(plain, q, k, v)
        assert out.dtype == exp.dtype == dtype
        assert _rel_err(out, exp) < tol
        for got, want in zip(grads, vjp_exp(g)):
            assert got.shape == want.shape
            assert _rel_err(got, want) < tol

    def test_block_rule(self):
        """Blocks divide S: a cap that does not is halved (to 128); a cap
        above S gives S."""
        b = ops.causal_blocks(640, (512, 256), (256, 512), (1024, 512))
        assert (b.block_q, b.block_k_major, b.block_q_major_dkv,
                b.block_k_major_dkv, b.block_q_dq, b.block_k_major_dq) == \
            (128, 128, 128, 128, 640, 128)
        b = ops.causal_blocks(4096)
        assert (b.block_q, b.block_k_major) == ops.FWD_BLOCKS
        assert (b.block_q_major_dkv, b.block_k_major_dkv) == ops.DKV_BLOCKS
        assert (b.block_q_dq, b.block_k_major_dq) == ops.DQ_BLOCKS
        assert b.has_backward_blocks


class TestAttentionDispatch:
    """Which path ``layers.attention`` lowers to, read from
    ``ATTENTION_PATHS``: the fused kernel only on a TPU target, for the
    training path's causal self-attention at ``S > q_chunk``,
    ``S % 128 == 0``, with no window, softcap, cache or encoder."""

    @staticmethod
    def _paths(fn, args, platform):
        from repro.models import layers
        layers.ATTENTION_PATHS.update(fused=0, jnp=0)
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=(platform,)).as_text()
        assert ("tpu_custom_call" in text) == bool(
            layers.ATTENTION_PATHS["fused"])
        return dict(layers.ATTENTION_PATHS)

    @staticmethod
    def _case(arch, S, *extra, B=2, **kw):
        """``attention`` of ``arch`` at ``B x S`` as a function of its
        parameters, its input and ``extra`` traced keyword arguments."""
        from repro.configs import get_config
        from repro.models.layers import attention, init_attn
        cfg = get_config(arch)
        p = jax.eval_shape(lambda: init_attn(jax.random.PRNGKey(0), cfg,
                                             jnp.float32))
        x = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        names = [n for n, _ in extra]

        def fn(p, x, *vals):
            return attention(cfg, p, x, pos, **kw,
                             **dict(zip(names, vals)))[0]

        return fn, (p, x, *(spec for _, spec in extra))

    @pytest.mark.parametrize("platform,path", [("tpu", "fused"),
                                               ("cpu", "jnp")])
    def test_internvl2_cell_shapes(self, platform, path):
        """The cell's shape (2 x 4,096, 14/2 heads of 64) differentiated
        as the train step does: fused on a TPU target, jnp on the CPU."""
        fn, args = self._case("internvl2-1b", 4096)
        paths = self._paths(
            jax.grad(lambda p, x: fn(p, x).astype(jnp.float32).sum(),
                     argnums=(0, 1)), args, platform)
        assert paths[path] > 0 and sum(paths.values()) == paths[path]

    @pytest.mark.parametrize("case", ["short", "not_128", "softcap",
                                      "cross", "decode", "traced_window"])
    def test_other_calls_stay_on_jnp(self, case):
        from repro.models.layers import init_kv_cache
        bf = jnp.bfloat16
        if case == "short":                      # S == q_chunk
            fn, args = self._case("internvl2-1b", 512)
        elif case == "not_128":
            fn, args = self._case("internvl2-1b", 1000)
        elif case == "softcap":
            fn, args = self._case("gemma2-9b", 1024)
        elif case == "cross":
            enc = jax.ShapeDtypeStruct((2, 1024, 384), bf)
            fn, args = self._case("whisper-tiny", 1024, ("enc_out", enc),
                                  rope=False)
        elif case == "decode":
            cache = jax.eval_shape(lambda: init_kv_cache(2, 1024, 2, 64, bf))
            fn, args = self._case("internvl2-1b", 1, ("cache", cache))
        else:                                    # hymba's scanned windows
            w = jax.ShapeDtypeStruct((), jnp.int32)
            fn, args = self._case("hymba-1.5b", 1024, ("window", w))
        assert self._paths(fn, args, "tpu") == {"fused": 0, "jnp": 1}

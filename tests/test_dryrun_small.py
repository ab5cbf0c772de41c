"""Miniature dry-run: the full lower+compile+roofline path on an 8-device
(2,2,2) mesh in a subprocess — fast CI coverage of launch/dryrun.py and
launch/roofline.py without the 512-device compile times."""
import os
import subprocess
import sys
import textwrap

import pytest


def _run(code: str, devices: int = 8, naive: bool = False) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = "src"
    if naive:
        env["REPRO_NAIVE_SHARDING"] = "1"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_COMMON = """
import jax, dataclasses
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.configs import get_config, input_specs, INPUT_SHAPES
from repro.dist import sharding as shd
from repro.dist.steps import make_serve_step, make_train_step
from repro.launch import roofline
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models.config import InputShape
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
"""


class TestMiniDryrun:
    @pytest.mark.parametrize("arch", ["llama3.2-1b", "deepseek-moe-16b",
                                      "xlstm-350m", "whisper-tiny"])
    def test_train_step_lowers_and_compiles(self, arch):
        out = _run(_COMMON + f"""
cfg = get_config("{arch}").reduced()
shape = InputShape("mini", 64, 8, "train")
model = build_model(cfg, max_seq=64)
params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
p_shard = shd.named(shd.param_specs(params_sds, mesh, cfg), mesh)
ocfg = AdamWConfig()
opt_sds = jax.eval_shape(partial(adamw.init, ocfg), params_sds)
o_shard = shd.named(shd.param_specs(opt_sds, mesh, cfg), mesh)
batch_sds = input_specs(cfg, shape)
b_shard = shd.named(shd.batch_specs(batch_sds, mesh), mesh)
step = make_train_step(model, ocfg)
with jax.set_mesh(mesh):
    c = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None)
                ).lower(params_sds, opt_sds, batch_sds).compile()
flops, byts = roofline.cost_terms(c)
assert flops > 0 and byts > 0
txt = c.as_text()
xf, xb = roofline.loop_cost_correction(txt)
stats = roofline.parse_collectives(txt)
print("OK", flops + xf, stats.total_bytes)
""")
        assert "OK" in out

    def test_decode_step_lowers_with_cache_sharding(self):
        out = _run(_COMMON + """
cfg = get_config("llama3.2-1b").reduced()
model = build_model(cfg, max_seq=64)
params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
p_shard = shd.named(shd.param_specs(params_sds, mesh, cfg), mesh)
cache_sds = jax.eval_shape(lambda: model.init_cache(8, 64))
c_shard = shd.named(shd.cache_specs(cache_sds, mesh), mesh)
serve = make_serve_step(model)
tok = jax.ShapeDtypeStruct((8,), jax.numpy.int32)
with jax.set_mesh(mesh):
    c = jax.jit(serve, in_shardings=(p_shard, c_shard, None, None),
                out_shardings=(None, None, c_shard), donate_argnums=(1,)
                ).lower(params_sds, cache_sds, tok, tok).compile()
print("OK", c.memory_analysis().temp_size_in_bytes >= 0)
""")
        assert "OK" in out

    def test_naive_vs_optimized_sharding_both_compile(self):
        code = _COMMON + """
cfg = get_config("internvl2-1b").reduced()
shape = InputShape("mini", 64, 8, "train")
model = build_model(cfg, max_seq=64)
params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
p_shard = shd.named(shd.param_specs(params_sds, mesh, cfg), mesh)
batch_sds = input_specs(cfg, shape)
b_shard = shd.named(shd.batch_specs(batch_sds, mesh), mesh)
with jax.set_mesh(mesh):
    c = jax.jit(model.prefill, in_shardings=(p_shard, b_shard)
                ).lower(params_sds, batch_sds).compile()
print("OK")
"""
        assert "OK" in _run(code, naive=False)
        assert "OK" in _run(code, naive=True)


class TestRooflineParser:
    def test_loop_multiplier_and_collective_expansion(self):
        """Scan of matmuls sharded over a mesh: the parser must expand the
        while trip count for both FLOPs and collective bytes."""
        out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import roofline

mesh = jax.make_mesh((8,), ("model",))
x = jnp.zeros((64, 64))
ws = jnp.zeros((16, 64, 64))

def f(x, ws):
    def body(x, w):
        return x @ w, None
    y, _ = jax.lax.scan(body, x, ws)
    return y

s = NamedSharding(mesh, P(None, "model"))
ws_s = NamedSharding(mesh, P(None, None, "model"))
c = jax.jit(f, in_shardings=(s, ws_s)).lower(x, ws).compile()
txt = c.as_text()
base_flops, _ = roofline.cost_terms(c)
xf, xb = roofline.loop_cost_correction(txt)
total = base_flops + xf
expected = 16 * 2 * 64 * 64 * 64 / 8      # 16 iterations, sharded /8
ratio = total / expected
assert 0.5 < ratio < 3.0, (total, expected)
stats = roofline.parse_collectives(txt)
print("OK", ratio, stats.total_count)
""")
        assert "OK" in out

    def test_invariant_weights_not_charged_per_iteration(self):
        from repro.launch.roofline import _invariant_names
        body = """
  %p = (f32[8,8], f32[4,8,8], s32[]) parameter(0)
  %w = f32[8,8]{1,0} get-tuple-element(%p), index=0
  %xs = f32[4,8,8]{2,1,0} get-tuple-element(%p), index=1
  %i = s32[] get-tuple-element(%p), index=2
  %x = f32[8,8]{1,0} dynamic-slice(%xs, %i), dynamic_slice_sizes={1,8,8}
  %y = f32[8,8]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (f32[8,8], f32[4,8,8], s32[]) tuple(%w, %xs, %i)
"""
        inv = _invariant_names(body)
        assert "w" in inv and "xs" in inv
        assert "i" in inv  # also passed through

    def test_dtype_table_covers_common_types(self):
        from repro.launch.roofline import _DTYPE_BYTES
        for dt, n in [("bf16", 2), ("f32", 4), ("s32", 4), ("pred", 1)]:
            assert _DTYPE_BYTES[dt] == n

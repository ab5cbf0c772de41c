"""Compile the scheduler's device programs for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
description, which refuses what the chip's compiler would refuse (tile
shapes, 64-bit types, unsupported in-kernel ops).  Each program compiles
at the paper's §7 Philly widths (20 servers, 160 jobs) and at the wide
cluster (128 servers, 512 jobs); Pallas kernels must lower to a Mosaic
``tpu_custom_call``, their plain-``jit`` twins must not.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers import every
test file.
"""
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import philly_cluster  # noqa: E402

ROWS = 64
WIDTHS = {"philly": (20, 160), "wide": (128, 512)}   # servers, jobs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _shapes(width):
    servers, jobs = WIDTHS[width]
    cluster = philly_cluster(servers, seed=0)
    return cluster.num_gpus, servers, jobs


def _compile(fn, args, one_chip, **static):
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    with jax.enable_x64(False):
        return fn.lower(*specs, **static).compile().as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("use_kernel", [True, False])
def test_pool_stats(one_chip, width, use_kernel):
    from repro.kernels.placement import _pool_stats_jit
    N, S, _ = _shapes(width)
    f32 = jnp.float32
    # The operand buffer: U, then t_lo, t_hi, rho_u, G, pid, need.
    text = _compile(_pool_stats_jit,
                    [((ROWS, N + 6), f32), ((N, S), f32), ((1, S), f32)],
                    one_chip, load_rel=4e-5, use_kernel=use_kernel,
                    interpret=False)
    assert ("tpu_custom_call" in text) == use_kernel


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("hetero", [False, True])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_score_probes(one_chip, width, hetero, use_kernel):
    from repro.kernels.placement import _score_probes_jit
    _, S, _ = _shapes(width)
    f32 = jnp.float32
    row = ((1, S), f32)
    # The operand buffer: Y, then f, gamma and the job's five terms.
    text = _compile(_score_probes_jit,
                    [((ROWS, S + 7), f32), row, row, row],
                    one_chip, hetero=hetero, b_inter=1.0, b_intra=10.0,
                    use_kernel=use_kernel, interpret=False)
    assert ("tpu_custom_call" in text) == use_kernel


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("per_candidate_terms", [False, True])
def test_stack_counts(one_chip, width, per_candidate_terms):
    """The Eq. (6) counts kernel behind ``tau_backend("kernel")``; the
    heterogeneous model shares it (its terms are host-side floats)."""
    from repro.kernels.tau import _stack_counts_jit
    _, S, J = _shapes(width)
    C = ROWS
    g_rows = C if per_candidate_terms else 1
    text = _compile(_stack_counts_jit,
                    [((C, J, S), jnp.int32), ((g_rows, J, 1), jnp.int32)],
                    one_chip, interpret=False)
    assert "tpu_custom_call" in text
    assert np.all([t not in text for t in ("f64[", "s64[")])


def _ring_rows(lowered: str, w: int, m: int) -> int:
    """Ops in lowered StableHLO that hold the ring's chunks as rows of a
    ``[w, m]`` array: slicing and writing rows there took the TPU
    compiler minutes at lane-aligned ``m`` (four for the bare 2 GB ring,
    over ten for the full-width step); as 1-D ranges, seconds."""
    return len(re.findall(rf"tensor<{w}x{m}x", lowered))


def _permutes(compiled: str) -> int:
    return (compiled.count("collective-permute(")
            + compiled.count("collective-permute-start("))


def test_ring_all_reduce_four_chips(topo):
    """The RAR exchange of internvl2-1b's 494,556,160-float gradient over
    the 2x2 host: 1-D chunks only, and 2(w-1) = 6 collective-permutes."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.dist.rar import ring_all_reduce
    w, n = 4, 494_556_160
    mesh = Mesh(np.asarray(topo.devices[:w]), ("data",))
    x = jax.ShapeDtypeStruct((w, n), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    lowered = jax.jit(jax.shard_map(
        lambda v: ring_all_reduce(v, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P("data"))).lower(x)
    assert _ring_rows(lowered.as_text(), w, n // w) == 0
    assert _permutes(lowered.compile().as_text()) == 2 * (w - 1)


def test_rar_train_step_four_chips_full_width(topo):
    """internvl2-1b's RAR step at w=4, one sequence per chip: its ring
    holds 1-D chunks only, exchanges with 6 collective-permutes, and fits
    a v5e's 16 GB with donated state."""
    from functools import partial

    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config, input_specs
    from repro.dist.steps import make_rar_train_step
    from repro.models import build_model
    from repro.models.config import InputShape
    from repro.optim import adamw
    from repro.optim.adamw import AdamWConfig

    w = 4
    cfg = get_config("internvl2-1b")
    model = build_model(cfg, max_seq=512)
    ocfg = AdamWConfig()
    mesh = Mesh(np.asarray(topo.devices[:w]), ("data",))

    def on(tree, spec):
        s = NamedSharding(mesh, spec)
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(partial(adamw.init, ocfg), params)
    batch = input_specs(cfg, InputShape("smoke", 512, 4, "train"))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    lowered = make_rar_train_step(model, ocfg, mesh).lower(
        on(params, P()), on(opt, P()), on(batch, P("data")))
    assert _ring_rows(lowered.as_text(), w, -(-n // w)) == 0
    compiled = lowered.compile()
    assert _permutes(compiled.as_text()) == 2 * (w - 1)
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held < 16e9, held


def test_internvl2_train_step_fused_attention(topo):
    """internvl2-1b's one-chip RAR step at the cell's 2 x 4,096 positions
    (two layers): the causal attention is the fused Pallas kernel, forward
    and backward, and no f32 score slab of a 512-query chunk over all
    4,096 keys is left."""
    import dataclasses
    from functools import partial

    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config, input_specs
    from repro.dist.steps import make_rar_train_step
    from repro.models import build_model
    from repro.models.config import InputShape
    from repro.optim import adamw
    from repro.optim.adamw import AdamWConfig

    cfg = dataclasses.replace(get_config("internvl2-1b"), n_layers=2,
                              n_patches=1792, tie_embeddings=False)
    model = build_model(cfg, max_seq=4096)
    ocfg = AdamWConfig()
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))

    def on(tree, spec):
        s = NamedSharding(mesh, spec)
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(partial(adamw.init, ocfg), params)
    batch = input_specs(cfg, InputShape("cell", 4096, 2, "train"))
    assert batch["tokens"].shape == (2, 4096 - 1792)
    text = make_rar_train_step(model, ocfg, mesh).lower(
        on(params, P()), on(opt, P()), on(batch, P("data"))
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"f32\[[0-9,]*512,4096\]", text)

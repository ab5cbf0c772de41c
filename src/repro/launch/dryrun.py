import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

This is the no-hardware proof that the distribution config is coherent:
every assigned architecture, at every assigned input shape, must lower and
compile against the production meshes —

    single-pod : (data=16, model=16)           = 256 chips
    multi-pod  : (pod=2, data=16, model=16)    = 512 chips

using ShapeDtypeStruct stand-ins (zero allocation).  For each pair we print
``memory_analysis()`` (does it fit 16 GB/chip?) and ``cost_analysis()``
FLOPs/bytes + parsed collective bytes (feeds EXPERIMENTS.md §Roofline).

Usage:
    python -m repro.launch.dryrun                      # full matrix, 1 pod
    python -m repro.launch.dryrun --multi-pod          # full matrix, 2 pods
    python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
    python -m repro.launch.dryrun --json out.json
"""
import argparse
import json
import sys
import time
import traceback
from functools import partial

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import (INPUT_SHAPES, ARCHS, cache_slots, get_config,
                           input_specs, supported_shapes)
from repro.dist import sharding as shd
from repro.dist.steps import make_serve_step, make_train_step
from repro.launch import roofline
from repro.launch.mesh import TARGET_KIND, make_production_mesh
from repro.models import build_model
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig


def build_jitted(arch: str, shape_name: str, mesh, *,
                 opt_overrides: dict | None = None):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    model = build_model(cfg, max_seq=min(shape.seq_len, 65536))
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = shd.named(shd.param_specs(params_sds, mesh, cfg), mesh)

    if shape.kind == "train":
        ocfg = AdamWConfig(**(opt_overrides or {}))
        opt_sds = jax.eval_shape(partial(adamw.init, ocfg), params_sds)
        o_shard = shd.named(shd.param_specs(opt_sds, mesh, cfg), mesh)
        batch_sds = input_specs(cfg, shape)
        b_shard = shd.named(shd.batch_specs(batch_sds, mesh), mesh)
        step = make_train_step(model, ocfg)
        # donate params+opt: the update is in-place on real hardware
        jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
        return jitted, (params_sds, opt_sds, batch_sds)

    if shape.kind == "prefill":
        batch_sds = input_specs(cfg, shape)
        b_shard = shd.named(shd.batch_specs(batch_sds, mesh), mesh)

        def prefill_step(params, batch):
            # serving prefill: sampling needs only the last position — the
            # full [B, S, V] logits slab is never materialised as output
            logits = model.prefill(params, batch)
            if os.environ.get("REPRO_NAIVE_SHARDING"):
                return logits                      # baseline: full slab out
            return logits[:, -1, :]

        jitted = jax.jit(prefill_step, in_shardings=(p_shard, b_shard),
                         out_shardings=None)
        return jitted, (params_sds, batch_sds)

    # decode: one new token against a seq_len KV cache / recurrent state
    B = shape.global_batch
    slots = cache_slots(cfg, shape)
    cache_sds = jax.eval_shape(lambda: model.init_cache(B, slots))
    seq_shard = shape.name == "long_500k"
    c_spec = shd.cache_specs(cache_sds, mesh, seq_shard=seq_shard)
    c_shard = shd.named(c_spec, mesh)
    io_sds = input_specs(cfg, shape)
    tok_spec = shd.named(shd.batch_specs(io_sds, mesh), mesh)
    serve = make_serve_step(model)
    # donate the cache: decode updates it in place
    jitted = jax.jit(
        serve,
        in_shardings=(p_shard, c_shard, tok_spec["tok"], tok_spec["pos"]),
        out_shardings=(None, None, c_shard), donate_argnums=(1,))
    return jitted, (params_sds, cache_sds, io_sds["tok"], io_sds["pos"])


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             opt_overrides: dict | None = None, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    t0 = time.time()
    # jax.set_mesh (not the bare `with mesh:`) exposes the abstract mesh to
    # trace time so in-model shard_hint constraints resolve axis names.
    with jax.set_mesh(mesh):
        jitted, args = build_jitted(arch, shape_name, mesh,
                                    opt_overrides=opt_overrides)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    t1 = time.time()
    flops, byts = roofline.cost_terms(compiled)
    hlo_text = compiled.as_text()
    xf, xb = roofline.loop_cost_correction(hlo_text)
    flops += xf
    byts += xb
    stats = roofline.parse_collectives(
        hlo_text, pod_size=256 if multi_pod else 0)
    mem = roofline.memory_peak(compiled)
    rl = roofline.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name,
        chips=mesh.devices.size,
        hlo_flops=flops, hlo_bytes=byts,
        collective_bytes=stats.total_bytes, collectives=stats,
        model_flops=roofline.model_step_flops(cfg, shape),
        per_device_hbm_peak=mem, device_kind=TARGET_KIND)
    row = rl.row()
    row["compile_s"] = round(t1 - t0, 1)
    row["collective_counts"] = stats.count_by_kind
    row["collective_bytes_by_kind"] = stats.bytes_by_kind
    row["dcn_bytes"] = stats.dcn_bytes
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"compile {row['compile_s']}s, "
              f"mem/device {mem/2**30:.2f} GiB, "
              f"flops/device {flops:.3e}, bytes/device {byts:.3e}, "
              f"collective {stats.total_bytes:.3e} B "
              f"({stats.total_count} ops), bottleneck={row['bottleneck']}")
        print(f"         memory_analysis: {compiled.memory_analysis()}")
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch (default all)")
    ap.add_argument("--shape", default=None, help="single shape (default all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", default=None, help="write rows to this file")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else sorted(ARCHS)
    rows, failures = [], []
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        cfg = get_config(arch)
        shapes = [args.shape] if args.shape else supported_shapes(cfg)
        for shape_name in shapes:
            if shape_name not in supported_shapes(cfg):
                print(f"[dryrun] SKIP {arch} x {shape_name} (DESIGN.md)")
                continue
            for mp in meshes:
                try:
                    rows.append(run_pair(arch, shape_name, multi_pod=mp))
                except Exception as e:                     # noqa: BLE001
                    traceback.print_exc()
                    failures.append((arch, shape_name, mp, repr(e)))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    print(f"\n[dryrun] {len(rows)} pairs compiled, {len(failures)} failed")
    for f_ in failures:
        print("  FAIL:", f_)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

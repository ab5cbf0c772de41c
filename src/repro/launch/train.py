"""End-to-end training driver.

Runs real steps on the devices present (``--devices N`` takes the first
N; ``--virtual-cpu`` makes N virtual CPU devices instead, to exercise the
RAR data-parallel mode without accelerators).

    PYTHONPATH=src python -m repro.launch.train \
        --arch llama3.2-1b --steps 300 --seq 256 --batch 8 --reduced

``--mode rar`` uses the paper-faithful explicit ring-all-reduce step;
``--mode pjit`` the production path.  Both donate params and optimizer
state to the step.  Checkpoints land in --ckpt-dir.
"""
from __future__ import annotations

import argparse
import os
import time


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the data")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced smoke variant (CPU-friendly)")
    ap.add_argument("--mode", choices=("pjit", "rar"), default="pjit")
    ap.add_argument("--devices", type=int, default=0,
                    help="ring width: the first N devices (0 = all); an "
                         "error when fewer are present")
    ap.add_argument("--virtual-cpu", action="store_true",
                    help="run --devices N virtual CPU devices (sets "
                         "XLA_FLAGS; must precede any jax use)")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def setup(args: argparse.Namespace):
    """``(cfg, model, ocfg, shape)`` for parsed arguments -- the pieces a
    caller needs to build a reference step on the same model."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.config import InputShape
    from repro.optim.adamw import AdamWConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = InputShape("cli", args.seq, args.batch, "train")
    model = build_model(cfg, max_seq=args.seq)
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=min(50, args.steps // 10 + 1),
                       total_steps=args.steps)
    return cfg, model, ocfg, shape


def batch_at(args: argparse.Namespace, cfg, shape, step: int) -> dict:
    """The global batch of ``step``, generated from ``--seed``."""
    import jax

    from repro.data import DataConfig, make_batch
    return jax.tree.map(jax.numpy.asarray,
                        make_batch(cfg, shape, step, DataConfig(seed=args.seed)))


def run(args: argparse.Namespace, on_step=None) -> dict:
    """Train ``args.steps`` steps; returns ``{"losses", "devices"}``.

    ``on_step(step, params, metrics)``, when given, sees each step's
    outputs (the params are the step's donated-into buffers)."""
    import jax
    import numpy as np

    from repro import ckpt
    from repro.dist.steps import (make_rar_train_step, make_train_step,
                                  replicate)
    from repro.launch.mesh import make_mesh, take_devices
    from repro.optim import adamw

    cfg, model, ocfg, shape = setup(args)
    devices = take_devices(args.devices)
    params = model.init(jax.random.PRNGKey(args.seed))
    n_params = sum(np.prod(l.shape) for l in jax.tree.leaves(params))
    print(f"[train] {cfg.name}{' (reduced)' if args.reduced else ''}: "
          f"{n_params/1e6:.1f}M params, {len(devices)} "
          f"{devices[0].platform} device(s), mode={args.mode}")
    opt = adamw.init(ocfg, params)

    if args.mode == "rar":
        if args.batch % len(devices):
            raise SystemExit(f"batch {args.batch} must divide over "
                             f"{len(devices)} devices")
        mesh = make_mesh((len(devices),), ("data",), devices=devices)
        params, opt = replicate((params, opt), mesh)
        step_fn = make_rar_train_step(model, ocfg, mesh)
    else:
        step_fn = jax.jit(make_train_step(model, ocfg), donate_argnums=(0, 1))

    losses = []
    t0 = time.time()
    for step in range(args.steps):
        params, opt, metrics = step_fn(params, opt,
                                       batch_at(args, cfg, shape, step))
        losses.append(float(metrics["loss"]))
        if on_step is not None:
            on_step(step, params, metrics)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"[train] step {step:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)")
        if args.ckpt_every and step and step % args.ckpt_every == 0:
            path = os.path.join(args.ckpt_dir, f"{cfg.name}_{step}.npz")
            ckpt.save(path, params=params, opt_state=opt, step=step)
            print(f"[train] checkpoint -> {path}")

    k = max(1, min(3, len(losses) // 2))
    first, last = np.mean(losses[:k]), np.mean(losses[-k:])
    print(f"[train] loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")
    return {"losses": losses, "devices": devices}


def main(argv=None) -> None:
    args = parse_args(argv)
    if args.virtual_cpu:
        from repro.launch.mesh import request_cpu_devices
        request_cpu_devices(max(args.devices, 1))
    from repro.launch.mesh import take_devices, use_compile_cache
    use_compile_cache(len(take_devices(args.devices)))
    run(args)


if __name__ == "__main__":
    main()

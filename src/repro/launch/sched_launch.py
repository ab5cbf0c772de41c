"""Scheduler-integrated multi-job launcher: SJF-BCO placing *real* JAX
RAR training jobs onto device slices.

This is the paper's full loop made executable: a multi-tenant "cluster" of
devices grouped into servers, a queue of RAR data-parallel training jobs
(reduced archs, ring widths cycling 1, 2, 4), SJF-BCO (or a baseline
policy) deciding placement and order, and each job actually training with
the explicit ring-all-reduce collective on a mesh built from exactly the
devices the scheduler assigned.

Jobs execute one after another in this process, so wall-clock contention
is not physical; the simulator provides the contention-aware makespan for
the chosen placement, and the launcher proves the placements are
*executable* (each job really trains on its assigned slice).

    PYTHONPATH=src python -m repro.launch.sched_launch \
        --devices 4 --servers 2 --jobs 3 --policy sjf-bco --steps 2

``--devices`` takes the first N devices present (an error when fewer
exist); ``--virtual-cpu`` makes N virtual CPU devices instead.
"""
from __future__ import annotations

import argparse
import time


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--virtual-cpu", action="store_true",
                    help="run --devices N virtual CPU devices (sets "
                         "XLA_FLAGS; must precede any jax use)")
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--jobs", type=int, default=6)
    ap.add_argument("--policy", default="sjf-bco",
                    choices=("sjf-bco", "ff", "ls", "rand", "reserved",
                             "sjf-bco-adaptive"))
    ap.add_argument("--steps", type=int, default=4,
                    help="real train steps per job (F_j for the simulator "
                         "is scaled from this)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(args: argparse.Namespace) -> list[dict]:
    """Schedule the queue and train every job on its assigned slice.

    Returns one record per job: ``jid``, ``arch``, the assigned ``gpus``,
    the ``devices`` its mesh was built on, and the first/last ``loss``."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.core import Cluster, Job, ScheduleRequest, get_policy, simulate
    from repro.data import DataConfig, make_batch
    from repro.dist.steps import make_rar_train_step, replicate
    from repro.launch.mesh import take_devices
    from repro.models import build_model
    from repro.models.config import InputShape
    from repro.optim import adamw
    from repro.optim.adamw import AdamWConfig

    if args.devices % args.servers:
        raise SystemExit("--devices must divide evenly into --servers")
    devices = np.asarray(take_devices(args.devices))
    per_srv = args.devices // args.servers
    cluster = Cluster(capacities=(per_srv,) * args.servers)

    # --- job queue: reduced archs, ring widths cycling 1, 2, 4 -------------
    rng = np.random.default_rng(args.seed)
    arch_pool = ["llama3.2-1b", "xlstm-350m", "internvl2-1b", "whisper-tiny",
                 "hymba-1.5b", "deepseek-moe-16b"]
    widths = [w for w in (1, 2, 4) if w <= args.devices]
    jobs, job_archs = [], []
    for j in range(args.jobs):
        jobs.append(Job(jid=j, num_gpus=widths[j % len(widths)],
                        iters=int(rng.integers(1000, 3000)),
                        grad_size=float(rng.uniform(5e-4, 2e-3)),
                        batch=32, dt_fwd=3e-4,
                        dt_bwd=float(rng.uniform(4e-3, 1.2e-2))))
        job_archs.append(arch_pool[j % len(arch_pool)])

    # --- schedule -----------------------------------------------------------
    sched = get_policy(args.policy)(
        ScheduleRequest(cluster=cluster, jobs=jobs, horizon=100000))
    sim = simulate(cluster, jobs, sched.assignment)
    print(f"[sched] policy={args.policy}: simulated makespan "
          f"{sim.makespan:.0f} slots, avg JCT {sim.avg_jct:.0f}, "
          f"peak contention {sim.peak_contention}")

    # --- execute each job on its assigned device slice ---------------------
    shape = InputShape("sched", args.seq, 0, "train")
    records = []
    for j, gpu_ids in sched.assignment:
        arch = job_archs[j]
        cfg = get_config(arch).reduced()
        w = len(gpu_ids)
        mesh = Mesh(devices[np.asarray(gpu_ids)], ("data",))
        model = build_model(cfg, max_seq=args.seq)
        params = model.init(jax.random.PRNGKey(args.seed + j))
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=args.steps)
        params, opt = replicate((params, adamw.init(ocfg, params)), mesh)
        step_fn = make_rar_train_step(model, ocfg, mesh)
        batch_size = max(w, 2)
        t0 = time.time()
        losses = []
        for step in range(args.steps):
            batch = make_batch(cfg, shape, step,
                               DataConfig(seed=args.seed + j),
                               batch_override=batch_size)
            batch = jax.tree.map(jax.numpy.asarray, batch)
            params, opt, metrics = step_fn(params, opt, batch)
            losses.append(float(metrics["loss"]))
        mesh_ids = [int(d.id) for d in mesh.devices.flat]
        srvs = sorted({int(g) // per_srv for g in gpu_ids})
        print(f"[sched] job {j:2d} ({arch:18s} w={w}) on devices "
              f"{list(map(int, gpu_ids))} (servers {srvs}, mesh device ids "
              f"{mesh_ids}): loss {losses[0]:.3f}->{losses[-1]:.3f} in "
              f"{time.time()-t0:.1f}s [start slot {sim.start[j]}, "
              f"finish {sim.finish[j]}]")
        records.append({"jid": j, "arch": arch,
                        "gpus": [int(g) for g in gpu_ids],
                        "devices": mesh_ids, "loss": (losses[0], losses[-1])})

    print(f"[sched] all {len(jobs)} jobs executed on their assigned slices")
    return records


def main(argv=None) -> list[dict]:
    args = parse_args(argv)
    if args.virtual_cpu:
        from repro.launch.mesh import request_cpu_devices
        request_cpu_devices(args.devices)
    from repro.launch.mesh import use_compile_cache
    use_compile_cache(args.devices)
    return run(args)


if __name__ == "__main__":
    main()

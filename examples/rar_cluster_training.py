"""End-to-end driver (deliverable b): schedule a queue of real training
jobs with SJF-BCO and EXECUTE each on its assigned device slice with the
explicit ring-all-reduce collective — then train the quickstart model for
a few hundred steps to show convergence.

This is `repro.launch.sched_launch` exercised as a library plus a longer
single-job training run.

Run:  PYTHONPATH=src python examples/rar_cluster_training.py
(uses 4 forced host devices; takes a few minutes on CPU)
"""
import os

# CPU-runnable: force 4 host devices so the ring collectives are real.
# Appends to (rather than clobbers) any XLA_FLAGS already in the env.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import Cluster, Job, ScheduleRequest, get_policy, simulate

from repro.configs import get_config
from repro.data import DataConfig, make_batch
from repro.dist.steps import make_rar_train_step
from repro.models import build_model
from repro.models.config import InputShape
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig

# ---- 1) a small multi-tenant cluster: 2 servers x 2 GPUs ------------------
cluster = Cluster(capacities=(2, 2))
queue = [
    ("llama3.2-1b", 2), ("whisper-tiny", 1), ("internvl2-1b", 2),
]
jobs = [Job(jid=i, num_gpus=g, iters=1500, grad_size=1e-3, batch=32,
            dt_fwd=3e-4, dt_bwd=8e-3) for i, (_, g) in enumerate(queue)]
sched = get_policy("sjf-bco")(
    ScheduleRequest(cluster=cluster, jobs=jobs, horizon=50000))
sim = simulate(cluster, jobs, sched.assignment)
print(f"[cluster] SJF-BCO makespan {sim.makespan:.0f} slots, "
      f"peak contention {sim.peak_contention}")

# ---- 2) execute every job on its assigned slice with explicit RAR --------
devices = np.asarray(jax.devices())
for j, gpu_ids in sched.assignment:
    arch, w = queue[j]
    cfg = get_config(arch).reduced()
    mesh = Mesh(devices[np.asarray(gpu_ids)], ("data",))
    model = build_model(cfg, max_seq=64)
    params = model.init(jax.random.PRNGKey(j))
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=3)
    opt = adamw.init(ocfg, params)
    step = make_rar_train_step(model, ocfg, mesh)
    shape = InputShape("ex", 64, max(2, len(gpu_ids)), "train")
    for s in range(3):
        batch = jax.tree.map(jnp.asarray, make_batch(cfg, shape, s,
                                                     DataConfig(seed=j)))
        params, opt, m = step(params, opt, batch)
    print(f"[job {j}] {arch:14s} ring w={len(gpu_ids)} on devices "
          f"{list(map(int, gpu_ids))}: loss {float(m['loss']):.3f} OK")

# ---- 3) a longer convergence run (a few hundred steps) -------------------
print("[long-run] llama3.2-1b reduced, 150 steps, RAR over 4 devices")
cfg = get_config("llama3.2-1b").reduced()
mesh = Mesh(devices, ("data",))
model = build_model(cfg, max_seq=64)
params = model.init(jax.random.PRNGKey(0))
ocfg = AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=150)
opt = adamw.init(ocfg, params)
step = make_rar_train_step(model, ocfg, mesh)
shape = InputShape("long", 64, 8, "train")
losses = []
for s in range(150):
    batch = jax.tree.map(jnp.asarray, make_batch(cfg, shape, s))
    params, opt, m = step(params, opt, batch)
    losses.append(float(m["loss"]))
    if s % 50 == 0 or s == 149:
        print(f"  step {s:3d} loss {losses[-1]:.4f}")
first, last = np.mean(losses[:20]), np.mean(losses[-20:])
print(f"[long-run] mean loss {first:.3f} -> {last:.3f}")
assert last < first - 0.5, "expected clear convergence over 300 steps"
print("rar_cluster_training OK")

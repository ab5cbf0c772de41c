"""Data-parallel RAR training cells: closed-loop steps of
``dist.steps.make_rar_train_step`` over ``launch.mesh.make_mesh`` of the
first ``width`` chips, fed by ``launch.train.batch_at``.

Set-up builds the one object the window drives -- the compiled step and
its state, with weights the benchmark makes on the device from the seed --
and drives it through its first three steps with the window's own call
and feed (rows that all differ).  It keeps what the comparison needs:
each step's loss, the per-leaf norms of the first clipped gradient as
the optimizer holds it (``m_1 / (1 - b1)``) and the per-leaf norms of the
parameters' change after step 3.  The window then runs steps 4, 5, ...
until ``--seconds`` have passed; ``train_tokens_per_s`` counts every
chip's tokens over the window.  After the window the program's state is
freed and the plain reference takes the same three steps from the same
weights and rows.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import time
from functools import partial

import numpy as np

from bench.lib import model_ref, traffic

# Keys of the configuration's "model" that the program's ModelConfig takes.
PROGRAM_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab", "n_patches", "rope_theta", "norm_eps",
                "tie_embeddings", "param_dtype", "compute_dtype", "remat")
SETUP_STEPS = 3


def build(run) -> dict:
    """The program's model, mesh and compiled-on-first-call step."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.dist.steps import make_rar_train_step
    from repro.launch import train as ltrain
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.models.config import InputShape
    from repro.optim import adamw
    from repro.optim.adamw import AdamWConfig

    cfg, tf = run.config, run.traffic
    m, o = cfg["model"], cfg["optimizer"]
    width, seq = tf["width"], tf["seq"]
    batch = width * tf["per_chip_batch"]
    pcfg = dataclasses.replace(get_config(cfg["program_arch"]),
                               **{k: m[k] for k in PROGRAM_KEYS})
    model = build_model(pcfg, max_seq=seq)
    ocfg = AdamWConfig(**{k: o[k] for k in (
        "lr", "b1", "b2", "eps", "weight_decay", "clip_norm", "warmup_steps",
        "total_steps", "min_lr_ratio")})
    want = model_ref.param_shapes(m)
    got = jax.tree.map(lambda x: tuple(x.shape),
                       jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    if got != want:
        raise ValueError(f"the program's parameters {got} are not the "
                         f"configuration's {want}")
    mesh = make_mesh((width,), ("data",), devices=run.devices[:width])
    rep = NamedSharding(mesh, P())
    n_params = sum(math.prod(s) for s in jax.tree.leaves(
        want, is_leaf=lambda x: isinstance(x, tuple)))
    run.readings.update(global_batch=batch, seq=seq,
                        grad_bytes=4.0 * n_params)
    return {
        "weights": jax.jit(partial(model_ref.make_weights, m),
                           out_shardings=rep),
        "opt_init": jax.jit(partial(adamw.init, ocfg), out_shardings=rep),
        "step_fn": make_rar_train_step(model, ocfg, mesh),
        "norms": jax.jit(model_ref.leaf_norms),
        "change": jax.jit(lambda a, b: model_ref.leaf_norms(
            jax.tree.map(lambda x, y: x - y, a, b))),
        "pcfg": pcfg, "shape": InputShape("bench", seq, batch, "train"),
        "batch_at": ltrain.batch_at, "batch": batch, "seq": seq,
        "b1": o["b1"]}


def start(run, built: dict, seed: int) -> dict:
    """Seeded state, driven through its first steps; keeps what the
    comparison needs."""
    state = dict(built, params=built["weights"](model_ref.seed_key(seed)),
                 args=argparse.Namespace(seed=seed), next=0, losses=[])
    state["opt"] = built["opt_init"](state["params"])
    for _ in range(SETUP_STEPS):
        _step(run, state)
        if state["next"] == 1:
            state["grad_norms"] = np.asarray(
                built["norms"](state["opt"]["m"])) / (1 - built["b1"])
    p0 = built["weights"](model_ref.seed_key(seed))
    state["change_norms"] = np.asarray(built["change"](state["params"], p0))
    del p0
    state["setup_losses"] = list(state["losses"])
    state["losses"].clear()
    return state


def setup(run) -> dict:
    return start(run, build(run), run.seed)


def _step(run, state) -> None:
    """One step through the window's own call and feed."""
    import jax
    with run.span("batch_at"):
        batch = state["batch_at"](state["args"], state["pcfg"],
                                  state["shape"], state["next"])
    with run.span("step"):
        state["params"], state["opt"], metrics = state["step_fn"](
            state["params"], state["opt"], batch)
        jax.block_until_ready((state["params"], state["opt"], metrics))
    state["losses"].append(float(metrics["loss"]))
    state["next"] += 1


def window(run, state) -> None:
    first = len(run.spans["batch_at"])
    t0 = time.perf_counter()
    steps = 0
    while steps == 0 or time.perf_counter() - t0 < run.seconds:
        _step(run, state)
        steps += 1
    elapsed = time.perf_counter() - t0
    run.attempted = steps
    run.e2e["train_tokens_per_s"] = \
        steps * state["batch"] * state["seq"] / elapsed
    run.readings.update(steps=steps, window_s=elapsed,
                        batch_at_window=(first, first + steps))


def traced(run, state) -> None:
    n = run.traffic["traced_steps"]
    for _ in range(n):
        _step(run, state)
    run.readings["traced_steps"] = n


def check(run, state) -> None:
    bad = sum(1 for x in state["losses"] if not math.isfinite(x))
    run.failed = bad
    state["params"] = state["opt"] = state["step_fn"] = None
    ref = reference(run, run.seed, state["batch"])
    for name, value in compare(state, ref).items():
        run.check(name, value, run.limits[name])
    run.check("nonfinite_losses", bad, 0)


def reference(run, seed: int, rows: int, fp8: bool = False) -> dict:
    """The plain reference's three steps on the first ``rows`` rows of
    each of the seed's batches."""
    cfg, tf = run.config, run.traffic
    m = cfg["model"]
    batches = []
    for t in range(SETUP_STEPS):
        b = traffic.vlm_batch(cfg["program_arch"], m["vocab"],
                              m["n_patches"], m["d_model"],
                              tf["width"] * tf["per_chip_batch"], tf["seq"],
                              seed, t, tf["zipf_a"])
        batches.append({k: v[:rows] for k, v in b.items()})
    return model_ref.reference_steps(m, cfg["optimizer"], seed, batches,
                                     block=tf["reference_block"], fp8=fp8,
                                     device=run.devices[0])


def as_program(ref: dict) -> dict:
    """A reference run's readings in the place of the program's (the
    control and the planted faults)."""
    return {"setup_losses": ref["losses"], "grad_norms": ref["grad_norms"],
            "change_norms": ref["change_norms"]}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared: the worst step's relative loss gap, and by
    the worst leaf the gap of the first gradient's norm and of the
    change's norm (leaves whose reference gradient is under a thousandth
    of the median leaf's move by round-off alone and are left out of the
    change)."""
    losses = prog["setup_losses"]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                       ref["losses"]))
    raw = ref["grad_raw_norms"]
    keep = raw >= 1e-3 * np.median(raw)
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": model_ref.worst_gap(prog["grad_norms"],
                                             ref["grad_norms"])[0],
        "change_norm_gap": model_ref.worst_gap(prog["change_norms"],
                                               ref["change_norms"], keep)[0],
    }

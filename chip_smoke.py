"""Bring-up smoke test: the scheduler's device programs and the RAR trainer
on a TPU, through the entry points a user calls.

    python3 chip_smoke.py [--seed N]          # one chip: phases 1 and 2
    python3 chip_smoke.py --chips 4 [--seed N]  # the cross-chip phase only

Phase 1 (schedule) runs SJF-BCO with ``placement="columnar"`` on the
paper's §7 Philly cluster (20 servers, 160 jobs), the same cluster with
per-server speed tiers and uplink classes, and a wide 128-server cluster
with 512 jobs, once with the compiled Pallas kernels and once with the
XLA programs.  Each schedule must equal the scalar host oracle's (same
assignment, theta, kappa and simulated makespan), and ``evaluate_many``
over 64 candidates under ``tau_backend("kernel")`` must equal the NumPy
engine.  Phase 2 (train) runs ``repro.launch.train --mode rar`` at the
full published width of internvl2-1b and checks it against
``make_train_step`` on the same batch.  With ``--chips 4`` only the
cross-chip path runs: the ring all-reduce against ``psum`` over a
gradient the size of internvl2-1b's, internvl2-1b's RAR step at w=4
against ``make_train_step`` on the concatenated batch, and the
``sched_launch`` loop with every job on the chips it was assigned.

Weights and data come from ``--seed``.  Without a TPU the script exits
non-zero and prints no result.  Any failed phase makes it exit 1; the last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

# Heterogeneous draw for the second Philly cluster: two GPU generations
# and shared vs isolated uplinks (the mixes of tests/test_hetero.py).
SPEED_TIERS = ((50.0, 0.5), (12.5, 0.5))
LINK_CLASSES = ((1.25, "shared", 0.5), (1.0, "isolated", 0.5))
TRAIN_ARCH = "internvl2-1b"
# Full published width; batch 8 x 512 tokens (256 patch + 256 text).
TRAIN_ARGS = ["--arch", TRAIN_ARCH, "--steps", "4", "--batch", "8",
              "--seq", "512"]
# The four-chip RAR step: one sequence per chip at the same width.
RAR4_ARGS = ["--arch", TRAIN_ARCH, "--steps", "1", "--batch", "4",
             "--seq", "512"]


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _permutes(hlo: str) -> int:
    """Collective-permutes in compiled HLO (TPU emits the async form)."""
    return hlo.count("collective-permute(") + hlo.count(
        "collective-permute-start(")


# --------------------------------------------------------------------------
# Phase 1: schedule
# --------------------------------------------------------------------------


def phase_schedule(seed: int) -> None:
    from benchmarks.common import mix_for
    from repro.core import ClusterSpec, philly_workload
    from repro.kernels import placement as kp

    philly_jobs = philly_workload(seed=seed)
    cases = [
        ("philly", ClusterSpec(num_servers=20, seed=seed).build(),
         philly_jobs),
        ("philly-hetero", ClusterSpec(num_servers=20, seed=seed,
                                      speed_tiers=SPEED_TIERS,
                                      link_classes=LINK_CLASSES).build(),
         philly_jobs),
        ("wide", ClusterSpec(num_servers=128, seed=seed).build(),
         philly_workload(seed=seed, mix=mix_for(512))),
    ]
    check(not kp._interpret(None), "Pallas would run in interpret mode")
    check_schedules(cases)
    check_lowering(cases[0][1], len(philly_jobs))
    check_evaluate_many(cases[0][1], cases[1][1], philly_jobs, seed)


def check_schedules(cases) -> None:
    """Columnar kernel/jit schedules equal the scalar oracle's."""
    from benchmarks._bench_util import same_schedule
    from repro.core import ScheduleRequest, get_policy, simulate
    from repro.kernels import placement as kp

    policy = get_policy("sjf-bco")
    for name, cluster, jobs in cases:
        horizon = max(1200, 12 * len(jobs))
        log(f"{name}: {cluster.num_servers} servers, {cluster.num_gpus} "
            f"GPUs, {len(jobs)} jobs, heterogeneous="
            f"{cluster.is_heterogeneous}")
        t0 = time.perf_counter()
        oracle = policy(ScheduleRequest(cluster=cluster, jobs=jobs,
                                        horizon=horizon,
                                        params={"placement": "scalar"}))
        t_oracle = time.perf_counter() - t0
        sim_o = simulate(cluster, jobs, oracle.assignment)
        log(f"  scalar oracle: {t_oracle:.3f}s theta={oracle.theta!r} "
            f"kappa={oracle.kappa} makespan={sim_o.makespan}")
        for backend in ("kernel", "jit"):
            before = dict(kp.DISPATCH_COUNTS)
            t0 = time.perf_counter()
            res = policy(ScheduleRequest(
                cluster=cluster, jobs=jobs, horizon=horizon,
                params={"placement": "columnar",
                        "columnar_backend": backend}))
            dt = time.perf_counter() - t0
            sim = simulate(cluster, jobs, res.assignment)
            d = {k: kp.DISPATCH_COUNTS[k] - before[k] for k in before}
            log(f"  columnar/{backend}: {dt:.3f}s theta={res.theta!r} "
                f"kappa={res.kappa} makespan={sim.makespan} device_calls="
                f"{d['device']} host_calls={d['host']} rows={d['rows']} "
                f"rechecked_rows={d['rechecked']}")
            check(same_schedule(res, oracle, check_theta=True),
                  f"{name}/{backend}: schedule differs from the oracle")
            check(sim.makespan == sim_o.makespan,
                  f"{name}/{backend}: simulated makespan differs")
            check(d["device"] > 0 and d["host"] == 0,
                  f"{name}/{backend}: not every batch ran on the device")
    counts = kp.compile_counts()
    log(f"compiled variants: {counts}")
    check(min(counts.values()) > 0, "a device program never compiled")


def check_lowering(cluster, n_jobs: int) -> None:
    """The kernels lower to Mosaic (one representative shape each)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import placement as kp
    from repro.kernels import tau as kt

    N, S = cluster.num_gpus, cluster.num_servers
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    with jax.enable_x64(False):
        pool = kp._pool_stats_jit.lower(
            sds((8, N + 6), f32), sds((N, S), f32), sds((1, S), f32),
            load_rel=1e-4, use_kernel=True, interpret=False).as_text()
        score = kp._score_probes_jit.lower(
            sds((8, S + 7), f32), sds((1, S), f32), sds((1, S), f32),
            sds((1, S), f32), hetero=True, b_inter=1.0, b_intra=10.0,
            use_kernel=True, interpret=False).as_text()
        counts_txt = kt._stack_counts_jit.lower(
            sds((64, n_jobs, S), i32), sds((1, n_jobs, 1), i32),
            interpret=False).as_text()
    for what, txt in (("pool", pool), ("score", score),
                      ("stack_counts", counts_txt)):
        check("tpu_custom_call" in txt, f"{what} kernel is not a Mosaic call")
    log("pool/score/stack_counts kernels lower to tpu_custom_call")


def check_evaluate_many(cluster, hetero_cluster, jobs, seed: int) -> None:
    """``evaluate_many`` over C = 64 candidates under the kernel backend
    equals the NumPy engine, homogeneous and heterogeneous."""
    import numpy as np

    from repro.core import evaluate_many, tau_backend
    from repro.kernels import tau as kt

    rng = np.random.default_rng(seed)
    C, S = 64, cluster.num_servers
    G = np.asarray([job.num_gpus for job in jobs])
    Y = np.zeros((C, len(jobs), S), dtype=np.int64)
    for c in range(C):
        for i, g in enumerate(G.tolist()):
            np.add.at(Y[c, i], rng.integers(S, size=g), 1)
    for name, cl in (("homogeneous", cluster),
                     ("heterogeneous", hetero_cluster)):
        ref = evaluate_many(cl, jobs, Y)
        t0 = time.perf_counter()
        with tau_backend("kernel"):
            kern = evaluate_many(cl, jobs, Y)
        dt = time.perf_counter() - t0
        p, n_srv = kt.stack_counts(G, Y)
        check(np.array_equal(p, ref.p) and np.array_equal(
            n_srv, (Y > 0).sum(axis=2)), f"{name}: p / n_srv differ")
        check(all(np.array_equal(getattr(ref, f), getattr(kern, f))
                  for f in ("p", "tau", "phi", "bandwidth")),
              f"{name}: evaluate_many(kernel) differs from NumPy")
        log(f"evaluate_many[{name}] C={C} J={len(jobs)} S={S}: p, n_srv, "
            f"tau, phi equal to NumPy ({dt:.3f}s)")


# --------------------------------------------------------------------------
# Phase 2: train internvl2-1b at full width through repro.launch.train
# --------------------------------------------------------------------------


# AdamW's first step from zero moments moves a weight by
# lr * (g / (|g| + eps) + wd * p).  Where the clipped gradient g is well
# above eps and above the reassociation noise of two ways of summing it
# -- |g| >= max(100 eps, STRONG_RMS * the leaf's rms gradient) -- the
# weight moves by lr to within 1%, and two correct steps agree on its
# update's sign and size.  The noise still flips a few: internvl2-1b's
# RAR step at w=4 on a TPU v5e disagreed with the single-program step on
# 1.6e-4 of its strong weights, while a ring that drops the all-reduce
# disagrees on about 0.3 of them (reduced model, CPU).  MAX_MISMATCH
# sits between the two.
STRONG_RMS = 0.1
UPDATE_TOL = 0.02          # of lr
MAX_MISMATCH = 1e-3        # share of the strong weights


def compare_update(p0, ref, got, ref_moment, ocfg, lr: float) -> dict:
    """The update ``got - p0`` against the reference update ``ref - p0``
    (host copies; ``ref_moment`` is the reference step's first moment,
    ``(1 - b1) * g``).  Counts the weights, those that moved by ``lr``
    to ``UPDATE_TOL * lr``, the strong ones (gradient well above noise),
    the strong ones whose update is not such a move agreeing with the
    reference's to ``UPDATE_TOL * lr``, and the largest update gap over
    every weight."""
    import jax
    import numpy as np

    out = {"weights": 0, "moved": 0, "strong": 0, "mismatch": 0,
           "max_gap": 0.0}
    for a0, ar, ag, m in zip(*(jax.tree.leaves(t)
                               for t in (p0, ref, got, ref_moment))):
        a0, ar, ag = (np.asarray(a, np.float32) for a in (a0, ar, ag))
        g = np.abs(np.asarray(m, np.float32)) / (1 - ocfg.b1)
        rms = float(np.sqrt(np.mean(np.square(g, dtype=np.float64))))
        strong = g >= max(100 * ocfg.eps, STRONG_RMS * rms)
        gap = np.abs(ag - ar)
        # |update + lr * wd * p| is the gradient part of the move: lr.
        move = np.abs(ag - a0 + lr * ocfg.weight_decay * a0)
        moved = np.abs(move - lr) <= UPDATE_TOL * lr
        bad = (gap > UPDATE_TOL * lr) | ~moved
        out["weights"] += a0.size
        out["moved"] += int(moved.sum())
        out["strong"] += int(strong.sum())
        out["mismatch"] += int((bad & strong).sum())
        out["max_gap"] = max(out["max_gap"], float(gap.max()))
    return out


def check_update(cmp: dict, lr: float, what: str) -> None:
    """Most weights moved by lr, the strong ones as the reference did;
    none by more than a sign flip of a near-zero gradient (2 lr) beyond
    the reference."""
    check(cmp["moved"] >= 0.5 * cmp["weights"],
          f"{what}: under half the weights moved by lr")
    check(cmp["strong"] >= 0.25 * cmp["weights"],
          f"{what}: under a quarter of the weights have a gradient above "
          "noise")
    check(cmp["mismatch"] <= MAX_MISMATCH * cmp["strong"],
          f"{what}: update differs from the reference step's")
    check(cmp["max_gap"] <= 2.5 * lr,
          f"{what}: a weight moved past the reference by over 2 lr")


def phase_train(seed: int) -> None:
    import jax

    from repro.dist.steps import make_train_step
    from repro.launch import train
    from repro.optim import adamw

    targs = train.parse_args(TRAIN_ARGS + [
        "--mode", "rar", "--devices", "1", "--seed", str(seed),
        "--log-every", "1"])
    cfg, model, ocfg, shape = train.setup(targs)
    log(f"{cfg.name}: d_model={cfg.d_model} layers={cfg.n_layers} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab}; batch {targs.batch} x seq {targs.seq}")

    # Reference: the single-program step on the same weights and batch;
    # it leaves only host copies, so the run has the chip to itself.
    params = model.init(jax.random.PRNGKey(seed))
    p0 = jax.device_get(params)
    opt = adamw.init(ocfg, params)
    t0 = time.perf_counter()
    ref_params, ref_opt, ref_m = jax.jit(make_train_step(model, ocfg),
                                         donate_argnums=(0, 1))(
        params, opt, train.batch_at(targs, cfg, shape, 0))
    ref = {"params": jax.device_get(ref_params),
           "moment": jax.device_get(ref_opt["m"]),
           "loss": float(ref_m["loss"]), "gnorm": float(ref_m["grad_norm"]),
           "lr": float(ref_m["lr"])}
    del params, opt, ref_params, ref_opt
    log(f"make_train_step: loss {ref['loss']:.6f} grad_norm "
        f"{ref['gnorm']:.6f} ({time.perf_counter() - t0:.1f}s with compile)")

    seen = {}

    def on_step(step, params, metrics):
        if step == 0:
            seen["params"] = jax.device_get(params)
            seen["loss"] = float(metrics["loss"])
            seen["gnorm"] = float(metrics["grad_norm"])

    t0 = time.perf_counter()
    out = train.run(targs, on_step=on_step)
    dt = time.perf_counter() - t0
    losses = out["losses"]
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    cmp = compare_update(p0, ref["params"], seen["params"], ref["moment"],
                         ocfg, ref["lr"])
    log(f"RAR w=1 step 0: loss {seen['loss']:.6f} grad_norm "
        f"{seen['gnorm']:.6f}; update vs ref: {cmp} (lr {ref['lr']:.3e}); "
        f"losses {losses}; {dt:.1f}s with compile; peak device memory "
        f"{peak} bytes")
    check(all(math.isfinite(v) for v in losses), "non-finite loss")
    # Same graph up to fusion order: the loss and the gradient norm agree
    # to f32 reassociation of bf16-compute partial sums (1e-3 / 1e-2
    # relative).
    check(abs(seen["loss"] - ref["loss"]) <= 1e-3 * abs(ref["loss"]),
          "RAR loss differs from make_train_step")
    check(abs(seen["gnorm"] - ref["gnorm"]) <= 1e-2 * ref["gnorm"],
          "RAR grad norm differs from make_train_step")
    check_update(cmp, ref["lr"], "RAR w=1")


# --------------------------------------------------------------------------
# Phase 3 (--chips 4): the cross-chip path
# --------------------------------------------------------------------------


def ring_elems() -> int:
    """Parameters of the training model: the gradient one ring moves."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    model = build_model(get_config(TRAIN_ARCH), max_seq=512)
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))


def phase_ring(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.dist.rar import ring_all_reduce
    from repro.launch.mesh import make_mesh

    w = 4
    mesh = make_mesh((w,), ("data",), devices=jax.devices()[:w])
    log(f"ring mesh device ids {[d.id for d in mesh.devices.flat]}")
    n = ring_elems()
    shard = NamedSharding(mesh, P("data"))
    x = jax.jit(lambda k: jax.random.normal(k, (w, n), jnp.float32),
                out_shardings=shard)(jax.random.PRNGKey(seed))
    # Two programs, not one: compiled together at this size, the ring and
    # psum take minutes to compile; apart, seconds.
    progs = {name: jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    ).lower(x).compile()
        for name, fn in (("ring", lambda v: ring_all_reduce(v, "data")),
                         ("psum", lambda v: jax.lax.psum(v, "data")))}
    permutes = _permutes(progs["ring"].as_text())
    out, secs = {}, {}
    for name, prog in progs.items():
        out[name] = prog(x).block_until_ready()          # warm
        t0 = time.perf_counter()
        prog(x).block_until_ready()
        secs[name] = time.perf_counter() - t0
    gap = float(jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))(
        out["ring"], out["psum"]))
    big = float(jax.jit(lambda v: jnp.max(jnp.abs(v)))(x))
    # Ring order vs psum order: w-1 reassociated f32 additions of values
    # below w * max|x| -- (w-1) * 2^-23 * w * max|x| bounds the gap.
    tol = (w - 1) * 2.0 ** -23 * w * big
    log(f"ring vs psum over {n} f32 per chip ({n * 4 / 1e9:.2f} GB): "
        f"max gap {gap:.3e} (bound {tol:.3e}), collective-permutes "
        f"{permutes} (expect {2 * (w - 1)}); one call: ring "
        f"{secs['ring']:.4f}s, psum {secs['psum']:.4f}s")
    check(permutes == 2 * (w - 1), "ring HLO does not hold 2(w-1) permutes")
    check(gap <= tol, "ring all-reduce disagrees with psum")


def phase_rar_step(seed: int) -> None:
    from functools import partial

    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.dist.steps import make_rar_train_step, make_train_step
    from repro.launch import train
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw

    w = 4
    targs = train.parse_args(RAR4_ARGS + ["--seed", str(seed)])
    cfg, model, ocfg, shape = train.setup(targs)
    batch = train.batch_at(targs, cfg, shape, 0)

    def fresh(sharding=None):
        """Seeded params and Adam state, made where ``sharding`` says."""
        params = jax.jit(model.init, out_shardings=sharding)(
            jax.random.PRNGKey(seed))
        return params, jax.jit(partial(adamw.init, ocfg),
                               out_shardings=sharding)(params)

    # The reference runs first (on one chip) and leaves only host copies,
    # so the RAR step has each chip's memory to itself.
    p0 = jax.device_get(jax.jit(model.init)(jax.random.PRNGKey(seed)))
    ref_p, ref_opt, ref_m = jax.jit(make_train_step(model, ocfg),
                                    donate_argnums=(0, 1))(*fresh(), batch)
    ref_p, ref_moment = jax.device_get((ref_p, ref_opt["m"]))
    ref_loss, ref_gn = float(ref_m["loss"]), float(ref_m["grad_norm"])
    lr = float(ref_m["lr"])
    del ref_opt
    mesh = make_mesh((w,), ("data",), devices=jax.devices()[:w])
    log(f"RAR step mesh device ids {[d.id for d in mesh.devices.flat]}")
    p, opt, m = make_rar_train_step(model, ocfg, mesh)(
        *fresh(NamedSharding(mesh, P())), batch)
    del opt
    p = jax.device_get(p)
    dl = abs(float(m["loss"]) - ref_loss)
    dg = abs(float(m["grad_norm"]) - ref_gn)
    cmp = compare_update(p0, ref_p, p, ref_moment, ocfg, lr)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    log(f"RAR w={w} vs make_train_step on the concatenated batch "
        f"({cfg.name}, batch {targs.batch} x {targs.seq}): loss "
        f"{ref_loss:.6f}, |loss gap| {dl:.3e}, |grad-norm gap| {dg:.3e}, "
        f"update vs ref: {cmp} (lr {lr:.3e}); peak device memory per chip "
        f"{peaks}")
    # Per-shard losses and gradients summed in ring order against one
    # fused program: f32 reassociation of bf16-compute partial sums
    # (1e-3 / 1e-2 relative).
    check(dl <= 1e-3 * abs(ref_loss), "RAR loss differs")
    check(dg <= 1e-2 * ref_gn, "RAR grad norm differs")
    check_update(cmp, lr, f"RAR w={w}")


def phase_sched_launch(seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch import sched_launch

    records = sched_launch.main([
        "--devices", "4", "--servers", "2", "--jobs", "3", "--steps", "2",
        "--seed", str(seed)])
    devices = jax.devices()
    for r in records:
        want = [devices[g].id for g in r["gpus"]]
        check(r["devices"] == want,
              f"job {r['jid']} ran on {r['devices']}, assigned {want}")
        check(all(np.isfinite(r["loss"])), f"job {r['jid']}: bad loss")
    widths = sorted(len(r["gpus"]) for r in records)
    check(widths == [1, 2, 4], f"job widths {widths}, want [1, 2, 4]")
    log(f"sched_launch: jobs of widths {widths} each ran on the chips "
        "it was assigned")


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip phase")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r}); refusing to run", file=sys.stderr)
        return 2
    from repro.launch.mesh import use_compile_cache
    cache = use_compile_cache(args.chips)
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) present", file=sys.stderr)
        return 2
    log(f"devices: {[(d.id, d.device_kind) for d in devices]}; "
        f"compile cache {cache}")
    phases = ([("ring", phase_ring), ("rar_step", phase_rar_step),
               ("sched_launch", phase_sched_launch)] if args.chips == 4
              else [("schedule", phase_schedule), ("train", phase_train)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(args.seed)
            log(f"phase {name}: ok ({time.perf_counter() - t0:.1f}s)")
        except Exception:                       # noqa: BLE001
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAILED ({time.perf_counter() - t0:.1f}s)")
        gc.collect()
    if failed:
        log(f"failed phases: {failed}")
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

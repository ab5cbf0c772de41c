"""Microbenchmark for the batched/incremental contention-model engines.

Four measurements per job count |J| (16 / 64 / 256 by default):

  1. *Scheduler pass*: SJF-BCO (Alg. 1, theta bisection + kappa sweep) plus
     the slot simulation, once per engine.  The "reference" engine is the
     original per-candidate ``evaluate()`` loop; "incremental" replaces
     every full [J, S] model pass with an O(S)-ish probe/row-update;
     "batched" scores multi-candidate decisions via ``evaluate_many``.
     Schedules are asserted identical across engines (they are bit-equal
     by construction; see tests/test_batched_contention.py).  Each engine
     row records the sweep/bisect modes the counters were measured under,
     so numbers stay comparable across PRs as defaults move.
  2. *Kappa sweep*: SJF-BCO end-to-end (schedule + simulate) with
     ``params={"sweep": "batched"}`` (all kappa branches of a theta forked
     off shared placed prefixes) vs ``"sequential"`` (one kappa at a time,
     the reference), both pinned to the sequential bisection so the sweep
     axis is isolated.  Schedules are asserted identical -- CI's bench
     smoke fails on divergence.  Acceptance bar: >= 2x end-to-end at
     |J| = 256.
  3. *Theta bisection*: SJF-BCO end-to-end with ``params={"bisect":
     "speculative"}`` (probe-ladder rounds scored through shared
     copy-on-write placement lineages, the default) vs ``"sequential"``
     (the one-theta-at-a-time Alg. 1 oracle).  The final (theta, kappa,
     placements) are asserted identical -- CI's bench smoke fails on
     divergence.
  4. *Columnar placement*: SJF-BCO end-to-end with
     ``params={"placement": "columnar"}`` (the whole sweep x bisect forest
     advanced as one [branches, S] array program: vectorised argmin picks,
     Eq. (16) pool checks and batched refined-rho re-checks, jit-fused
     per step -- the "auto" backend resolves to "jit" on CPU) vs
     ``"scalar"`` (the per-branch
     ``try_place`` walk -- the oracle, and the faster CPU path at every
     measured size).  The final (theta, kappa, placements) are asserted
     identical -- CI's bench smoke fails on divergence.  Each row
     records ``scalar_s`` / ``columnar_s`` / ``winner``; the section's
     ``placement_crossover_J`` is the smallest measured |J| where
     columnar wins, or null when the scalar walk wins throughout.  The
     full run sweeps |J| = 256 / 1024 / 4096 / 16384; ``--scale`` adds
     a ``scale`` section with the |J| = 100000 schedule+simulate point
     (jit-columnar AND scalar, bit-identity asserted, simulated against
     a seeded Pareto arrival stream) which ``write_report`` preserves
     across reruns without the flag.
  5. *Kernel microbench*: ``evaluate_many`` on a [C, J, S] stack vs a
     Python loop of C ``evaluate()`` calls over the same placements.
  6. *Heterogeneity*: a cluster whose per-GPU ``gpu_speeds`` / per-server
     ``links`` arrays merely restate the homogeneous scalars is asserted
     bit-identical to the scalar cluster (schedule AND SimEvent stream --
     the degenerate-identity contract of the hetero refactor, enforced in
     CI via ``--quick``), plus one mixed-tier timing point recording what
     the generalized Eq. (8) terms cost end-to-end.

Emits ``BENCH_contention.json`` -- part of the repo's perf trajectory --
with wall-clock numbers and the model-evaluation counters (engine
acceptance bar: >= 5x fewer full-model evaluations at |J| = 256).

Usage::

    PYTHONPATH=src python benchmarks/bench_contention.py \
        [--quick] [--scale] [--out F]
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core import (ScheduleRequest, eval_counts, evaluate,
                        evaluate_many, get_policy, reset_eval_counts,
                        simulate)
try:                                    # run as a module: -m benchmarks....
    from benchmarks._bench_util import (check_identical, make_parser,
                                        philly_case, timed, write_report)
except ImportError:                     # run as a script from benchmarks/
    from _bench_util import (check_identical, make_parser, philly_case,
                             timed, write_report)

ENGINES = ("reference", "incremental", "batched")


def bench_scheduler(n_jobs: int, seed: int = 1) -> dict:
    cluster, jobs = philly_case(n_jobs, seed)
    horizon = max(1200, 12 * n_jobs)
    row: dict = {"J": n_jobs, "engines": {}}
    schedules = {}
    for engine in ENGINES:
        request = ScheduleRequest(cluster=cluster, jobs=jobs,
                                  horizon=horizon,
                                  params={"engine": engine})
        reset_eval_counts()
        t0 = time.perf_counter()
        sched = get_policy("sjf-bco")(request)
        t_sched = time.perf_counter() - t0
        t0 = time.perf_counter()
        sim = simulate(cluster, jobs, sched.assignment, engine=engine)
        t_sim = time.perf_counter() - t0
        counts = eval_counts()
        schedules[engine] = sched
        row["engines"][engine] = {
            "schedule_s": round(t_sched, 4),
            "simulate_s": round(t_sim, 4),
            # The active sweep/bisect/placement/stepping modes these
            # counters were measured under (the request defaults);
            # recorded per row so numbers stay comparable across PRs as
            # defaults move.
            "sweep_mode": "batched",
            "bisect_mode": "speculative",
            "placement_mode": "scalar",
            "sim_stepping": "multi" if engine != "reference" else "single",
            "est_makespan": sched.est_makespan,
            "sim_makespan": sim.makespan,
            **counts,
        }
    ref = schedules["reference"]
    for engine in ENGINES[1:]:
        # Hard failure, not just a report field: CI's bench-smoke step
        # relies on this to catch engine divergence.
        row["engines"][engine]["schedule_identical_to_reference"] = \
            check_identical(
                ref, schedules[engine],
                f"{engine} schedule diverged from reference at J={n_jobs}")
    ref_e = row["engines"]["reference"]
    inc_e = row["engines"]["incremental"]
    # "Full-model evaluations": complete [J, S] passes.  The incremental
    # engine replaces them with O(S) probes / row updates; evaluate_many
    # calls count once each (one fused pass).
    ref_full = ref_e["full"] + ref_e["batched_calls"]
    inc_full = inc_e["full"] + inc_e["batched_calls"]
    row["full_eval_reduction"] = round(ref_full / max(1, inc_full), 1)
    row["wall_speedup"] = round(
        (ref_e["schedule_s"] + ref_e["simulate_s"])
        / max(1e-9, inc_e["schedule_s"] + inc_e["simulate_s"]), 2)
    return row


def bench_sweep(n_jobs: int, seed: int = 1) -> dict:
    """SJF-BCO end-to-end: batched (shared-prefix) vs sequential kappa
    sweep, both on the default incremental engine and both pinned to the
    sequential bisection so only the sweep axis varies.  Both run the
    default scalar placement walk (the columnar axis has its own
    section, :func:`bench_placement`)."""
    cluster, jobs = philly_case(n_jobs, seed)
    horizon = max(1200, 12 * n_jobs)
    row: dict = {"J": n_jobs, "bisect_mode": "sequential", "modes": {}}
    schedules = {}
    for sweep in ("sequential", "batched"):
        request = ScheduleRequest(cluster=cluster, jobs=jobs,
                                  horizon=horizon,
                                  params={"sweep": sweep,
                                          "bisect": "sequential"})
        t0 = time.perf_counter()
        sched = get_policy("sjf-bco")(request)
        t_sched = time.perf_counter() - t0
        t0 = time.perf_counter()
        sim = simulate(cluster, jobs, sched.assignment)
        t_sim = time.perf_counter() - t0
        schedules[sweep] = sched
        row["modes"][sweep] = {
            "schedule_s": round(t_sched, 4),
            "simulate_s": round(t_sim, 4),
            "end_to_end_s": round(t_sched + t_sim, 4),
            "placement_mode": "scalar",
            "est_makespan": sched.est_makespan,
            "sim_makespan": sim.makespan,
        }
    # Hard failure, not just a report field: CI's bench-smoke step relies
    # on this to catch batched-sweep divergence.
    row["batched_identical_to_sequential"] = check_identical(
        schedules["sequential"], schedules["batched"],
        f"batched sweep diverged from sequential at J={n_jobs}",
        check_theta=True)
    row["end_to_end_speedup"] = round(
        row["modes"]["sequential"]["end_to_end_s"]
        / max(1e-9, row["modes"]["batched"]["end_to_end_s"]), 2)
    return row


def bench_bisect(n_jobs: int, seed: int = 1) -> dict:
    """SJF-BCO end-to-end: speculative vs sequential theta bisection,
    both on the default incremental engine, batched kappa sweep and
    scalar placement."""
    cluster, jobs = philly_case(n_jobs, seed)
    horizon = max(1200, 12 * n_jobs)
    row: dict = {"J": n_jobs, "sweep_mode": "batched",
                 "placement_mode": "scalar", "modes": {}}
    schedules = {}
    for bisect_mode in ("sequential", "speculative"):
        request = ScheduleRequest(cluster=cluster, jobs=jobs,
                                  horizon=horizon,
                                  params={"bisect": bisect_mode})
        t0 = time.perf_counter()
        sched = get_policy("sjf-bco")(request)
        t_sched = time.perf_counter() - t0
        t0 = time.perf_counter()
        sim = simulate(cluster, jobs, sched.assignment)
        t_sim = time.perf_counter() - t0
        schedules[bisect_mode] = sched
        row["modes"][bisect_mode] = {
            "schedule_s": round(t_sched, 4),
            "simulate_s": round(t_sim, 4),
            "end_to_end_s": round(t_sched + t_sim, 4),
            "theta": sched.theta,
            "kappa": sched.kappa,
            "est_makespan": sched.est_makespan,
            "sim_makespan": sim.makespan,
        }
    # Hard failure, not just a report field: CI's bench-smoke step relies
    # on this to catch speculative-bisection divergence from the oracle.
    row["speculative_identical_to_sequential"] = check_identical(
        schedules["sequential"], schedules["speculative"],
        f"speculative bisection diverged from sequential at J={n_jobs}",
        check_theta=True)
    row["end_to_end_speedup"] = round(
        row["modes"]["sequential"]["end_to_end_s"]
        / max(1e-9, row["modes"]["speculative"]["end_to_end_s"]), 2)
    return row


def bench_placement(n_jobs: int, seed: int = 1,
                    backend: str = "auto") -> dict:
    """SJF-BCO end-to-end: columnar branch-vectorised placement (the
    whole sweep x bisect forest as one [branches, S] array program,
    jit-fused per step when ``backend`` resolves to "jit") vs the
    scalar per-branch walk, identical modes otherwise (incremental
    engine, batched sweep, speculative bisection; each placement runs
    its own ladder defaults -- see ``bisect_levels``).  Schedules are
    asserted bit-identical (the jitted-columnar == scalar hard assert
    of CI's ``--quick`` smoke).

    Each row records ``scalar_s`` / ``columnar_s`` / ``winner`` so the
    report states explicitly, per size, which engine the measured
    crossover favours; ``main`` folds these into the section-level
    ``crossover_J``.  On this CPU host the scalar walk's copy-on-write
    lineages win at every measured size (the columnar row is the
    number to watch across PRs -- it is the trace-scale array engine
    that accelerator work builds on); record what is measured, not
    what is hoped."""
    from repro.core.api import resolve_columnar_backend
    cluster, jobs = philly_case(n_jobs, seed)
    horizon = max(1200, 12 * n_jobs)
    backend = resolve_columnar_backend({"columnar_backend": backend})
    row: dict = {"J": n_jobs, "sweep_mode": "batched",
                 "bisect_mode": "speculative",
                 "columnar_backend": backend, "modes": {}}
    schedules = {}
    for placement in ("scalar", "columnar"):
        request = ScheduleRequest(
            cluster=cluster, jobs=jobs, horizon=horizon,
            params={"placement": placement,
                    "columnar_backend": backend})
        sched, t_sched = timed(lambda req=request:
                               get_policy("sjf-bco")(req))
        sim, t_sim = timed(lambda a=sched.assignment:
                           simulate(cluster, jobs, a))
        schedules[placement] = sched
        row["modes"][placement] = {
            "schedule_s": round(t_sched, 4),
            "simulate_s": round(t_sim, 4),
            "end_to_end_s": round(t_sched + t_sim, 4),
            "theta": sched.theta,
            "kappa": sched.kappa,
            "est_makespan": sched.est_makespan,
            "sim_makespan": sim.makespan,
        }
    # Hard failure, not just a report field: CI's bench-smoke step
    # relies on this to catch (jitted-)columnar divergence from the
    # scalar oracle.
    row["columnar_identical_to_scalar"] = check_identical(
        schedules["scalar"], schedules["columnar"],
        f"columnar placement diverged from scalar at J={n_jobs}",
        check_theta=True)
    row["scalar_s"] = row["modes"]["scalar"]["schedule_s"]
    row["columnar_s"] = row["modes"]["columnar"]["schedule_s"]
    row["winner"] = ("columnar" if row["columnar_s"] < row["scalar_s"]
                     else "scalar")
    row["schedule_speedup"] = round(
        row["scalar_s"] / max(1e-9, row["columnar_s"]), 2)
    return row


def bench_scale(n_jobs: int = 100_000, seed: int = 1) -> dict:
    """The |J| = 1e5 point: one batch SJF-BCO pass through the
    jit-fused columnar placement, then a simulation of the resulting
    schedule against a seeded heavy-tailed Pareto arrival stream
    (``ArrivalSpec(kind="pareto")`` -- many near-zero gaps punctuated
    by long lulls, mean-normalised to 0.5 jobs/slot).  Runs the scalar
    walk on the same instance too, so the scalar-vs-columnar question
    is answered by measurement at this scale rather than extrapolated
    from the placement section's smaller sizes.  Behind ``--scale``
    only (minutes of wall clock); ``write_report`` preserves the
    section across reruns without the flag."""
    from repro.core import ArrivalSpec
    cluster, jobs = philly_case(n_jobs, seed)
    jobs = [dataclasses.replace(j, jid=i)
            for i, j in enumerate(jobs[:n_jobs])]
    arrivals = ArrivalSpec(kind="pareto", rate=0.5, seed=seed,
                           shape=1.5).build(jobs)
    horizon = max(1200, 12 * n_jobs)
    row: dict = {"J": n_jobs, "sweep_mode": "batched",
                 "bisect_mode": "speculative",
                 "arrivals": {"kind": "pareto", "rate": 0.5,
                              "shape": 1.5, "seed": seed,
                              "last_arrival": int(arrivals[-1])},
                 "modes": {}}
    schedules = {}
    for placement, params in (
            ("columnar", {"placement": "columnar",
                          "columnar_backend": "jit"}),
            ("scalar", {"placement": "scalar"})):
        request = ScheduleRequest(cluster=cluster, jobs=jobs,
                                  horizon=horizon, params=params)
        sched, t_sched = timed(lambda req=request:
                               get_policy("sjf-bco")(req))
        sim, t_sim = timed(lambda a=sched.assignment:
                           simulate(cluster, jobs, a, arrivals=arrivals))
        schedules[placement] = sched
        row["modes"][placement] = {
            "schedule_s": round(t_sched, 4),
            "simulate_s": round(t_sim, 4),
            "theta": sched.theta,
            "kappa": sched.kappa,
            "completed": sim.completed,
            "sim_makespan": sim.makespan,
        }
        print(f"scale |J|={n_jobs}: {placement} schedule "
              f"{t_sched:.1f}s simulate {t_sim:.1f}s "
              f"completed={sim.completed}", flush=True)
    row["columnar_identical_to_scalar"] = check_identical(
        schedules["scalar"], schedules["columnar"],
        f"columnar placement diverged from scalar at J={n_jobs}",
        check_theta=True)
    row["winner"] = (
        "columnar" if row["modes"]["columnar"]["schedule_s"]
        < row["modes"]["scalar"]["schedule_s"] else "scalar")
    return row


def bench_hetero(n_jobs: int, seed: int = 1) -> dict:
    """Degenerate-hetero identity (hard assert) + one mixed-tier point.

    A cluster whose ``gpu_speeds``/``links`` restate the scalars must be
    bit-identical to the scalar cluster -- schedule and simulation both
    (CI's bench smoke runs this under ``--quick``).  The mixed-tier row
    then times SJF-BCO + simulate on a genuinely heterogeneous cluster
    (half the servers at quarter speed, half the uplinks isolated), so
    the cost of the generalized Eq. (8) terms is tracked across PRs."""
    cluster, jobs = philly_case(n_jobs, seed)
    uniform = dataclasses.replace(
        cluster,
        gpu_speeds=(cluster.gpu_speed,) * cluster.num_gpus,
        links=((cluster.b_inter, "shared"),) * cluster.num_servers)
    assert not uniform.is_heterogeneous
    horizon = max(1200, 12 * n_jobs)
    row: dict = {"J": n_jobs, "modes": {}}
    schedules, sims = {}, {}
    for name, cl in (("scalar", cluster), ("degenerate", uniform)):
        request = ScheduleRequest(cluster=cl, jobs=jobs, horizon=horizon)
        sched, t_sched = timed(lambda req=request:
                               get_policy("sjf-bco")(req))
        sim, t_sim = timed(lambda c=cl, a=sched.assignment:
                           simulate(c, jobs, a))
        schedules[name], sims[name] = sched, sim
        row["modes"][name] = {
            "schedule_s": round(t_sched, 4),
            "simulate_s": round(t_sim, 4),
            "sim_makespan": sim.makespan,
        }
    # Hard failure, not just a report field: CI's bench-smoke step relies
    # on this to catch degenerate-hetero divergence from the scalars.
    row["degenerate_identical_to_scalar"] = check_identical(
        schedules["scalar"], schedules["degenerate"],
        f"degenerate hetero cluster diverged from scalars at J={n_jobs}",
        check_theta=True)
    if sims["scalar"].events != sims["degenerate"].events:
        raise AssertionError(
            f"degenerate hetero SimEvent stream diverged at J={n_jobs}")
    # Mixed tiers: half the servers at quarter speed, half isolated.
    speeds, links = [], []
    for s, cap in enumerate(cluster.capacities):
        speeds += [cluster.gpu_speed * (0.25 if s % 2 else 1.0)] * cap
        links.append((cluster.b_inter, "isolated" if s % 2 else "shared"))
    mixed = dataclasses.replace(cluster, gpu_speeds=tuple(speeds),
                                links=tuple(links))
    request = ScheduleRequest(cluster=mixed, jobs=jobs, horizon=horizon)
    sched, t_sched = timed(lambda req=request: get_policy("sjf-bco")(req))
    sim, t_sim = timed(lambda a=sched.assignment:
                       simulate(mixed, jobs, a))
    row["modes"]["mixed"] = {
        "schedule_s": round(t_sched, 4),
        "simulate_s": round(t_sim, 4),
        "sim_makespan": sim.makespan,
    }
    row["mixed_overhead"] = round(
        row["modes"]["mixed"]["schedule_s"]
        / max(1e-9, row["modes"]["scalar"]["schedule_s"]), 2)
    return row


def bench_evaluate_many(n_jobs: int, n_cands: int = 64, seed: int = 0,
                        repeats: int = 5) -> dict:
    """evaluate_many on [C, J, S] vs a loop of C evaluate() calls."""
    rng = np.random.default_rng(seed)
    cluster, jobs = philly_case(n_jobs, seed)
    S = cluster.num_servers
    stack = np.zeros((n_cands, len(jobs), S), dtype=np.int64)
    for c in range(n_cands):
        for i, job in enumerate(jobs):
            for _ in range(job.num_gpus):
                stack[c, i, rng.integers(S)] += 1
    t_loop = t_many = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for c in range(n_cands):
            evaluate(cluster, jobs, stack[c])
        t_loop = min(t_loop, time.perf_counter() - t0)
        t0 = time.perf_counter()
        many = evaluate_many(cluster, jobs, stack)
        t_many = min(t_many, time.perf_counter() - t0)
    # sanity: the batch result matches the loop on the last candidate
    assert np.array_equal(many.tau[-1],
                          evaluate(cluster, jobs, stack[-1]).tau)
    return {"J": n_jobs, "C": n_cands,
            "loop_s": round(t_loop, 4), "batched_s": round(t_many, 4),
            "speedup": round(t_loop / max(1e-9, t_many), 2)}


def main() -> None:
    ap = make_parser(__doc__, "BENCH_contention.json")
    ap.add_argument("--scale", action="store_true",
                    help="add the |J|=100000 schedule+simulate point "
                         "(minutes; excluded from --quick)")
    args = ap.parse_args()

    sizes = [16, 64] if args.quick else [16, 64, 256]
    report = {"bench": "contention-engine",
              "quick": args.quick,
              "scheduler": [], "sweep": [], "bisect": [],
              "placement": [], "evaluate_many": [], "hetero": []}
    for n in sizes:
        row = bench_scheduler(n)
        report["scheduler"].append(row)
        inc = row["engines"]["incremental"]
        print(f"|J|={n:4d}  ref {row['engines']['reference']['schedule_s']:.2f}s"
              f"  inc {inc['schedule_s']:.2f}s"
              f"  wall x{row['wall_speedup']:.2f}"
              f"  full-evals x{row['full_eval_reduction']:.0f} fewer"
              f"  identical={inc['schedule_identical_to_reference']}")
    for n in sizes:
        row = bench_sweep(n)
        report["sweep"].append(row)
        print(f"sweep |J|={n:4d}: sequential "
              f"{row['modes']['sequential']['end_to_end_s']:.2f}s"
              f"  batched {row['modes']['batched']['end_to_end_s']:.2f}s"
              f"  x{row['end_to_end_speedup']:.2f}"
              f"  identical={row['batched_identical_to_sequential']}")
    for n in sizes:
        row = bench_bisect(n)
        report["bisect"].append(row)
        print(f"bisect |J|={n:4d}: sequential "
              f"{row['modes']['sequential']['end_to_end_s']:.2f}s"
              f"  speculative {row['modes']['speculative']['end_to_end_s']:.2f}s"
              f"  x{row['end_to_end_speedup']:.2f}"
              f"  identical={row['speculative_identical_to_sequential']}")
    # Jitted-columnar-vs-scalar identity is part of the --quick CI
    # smoke too (hard assert inside bench_placement; "auto" resolves to
    # the jit backend on CPU).
    for n in (sizes if args.quick else [256, 1024, 4096, 16384]):
        row = bench_placement(n)
        report["placement"].append(row)
        print(f"placement |J|={n:5d}: scalar {row['scalar_s']:.2f}s"
              f"  columnar[{row['columnar_backend']}] "
              f"{row['columnar_s']:.2f}s"
              f"  winner={row['winner']}"
              f"  identical={row['columnar_identical_to_scalar']}")
    # The explicit crossover: smallest measured |J| where the columnar
    # engine beats the scalar walk, or null when the scalar walk wins
    # at every measured size (the honest answer on this CPU host).
    won = [r["J"] for r in report["placement"] if r["winner"] == "columnar"]
    report["placement_crossover_J"] = min(won) if won else None
    print(f"placement crossover |J| = {report['placement_crossover_J']}")
    if args.scale and not args.quick:
        report["scale"] = [bench_scale(100_000)]
    for n in sizes:
        row = bench_evaluate_many(n, n_cands=16 if args.quick else 64)
        report["evaluate_many"].append(row)
        print(f"evaluate_many |J|={n:4d} C={row['C']}: loop {row['loop_s']}s"
              f" batched {row['batched_s']}s  x{row['speedup']:.1f}")
    # Degenerate-hetero identity is part of the --quick CI smoke too
    # (hard asserts inside bench_hetero).
    for n in sizes:
        row = bench_hetero(n)
        report["hetero"].append(row)
        print(f"hetero |J|={n:4d}: scalar "
              f"{row['modes']['scalar']['schedule_s']:.2f}s"
              f"  mixed {row['modes']['mixed']['schedule_s']:.2f}s"
              f"  x{row['mixed_overhead']:.2f}"
              f"  identical={row['degenerate_identical_to_scalar']}")

    write_report(report, args.out)


if __name__ == "__main__":
    main()

"""Batch re-plan cells: a closed loop of SJF-BCO re-plans.

The traffic file gives the policy, its ``params`` (e.g. the columnar
placement engine) and the pool size.  Set-up draws the configuration's
job mix, permutes it once per pool member from the seed, and re-plans
every member once, so every program the window drives is compiled and
warm.  The window cycles through the pool, one re-plan at a time, until
``--seconds`` have passed and every member has been re-planned equally
often; ``replan_s`` is the window's length over the re-plans in it.
Each re-plan of the window is compared, field for field, with the plain
reference's schedule of its pool member.
"""
from __future__ import annotations

import sys
import time

from bench.lib import sched_ref, traffic


def _cluster(cfg: dict):
    from repro.core import Cluster
    c = cfg["cluster"]
    return Cluster(capacities=tuple(c["capacities"]), b_intra=c["b_intra"],
                   b_inter=c["b_inter"], gpu_speed=c["gpu_speed"],
                   xi1=c["xi1"], xi2=c["xi2"], alpha=c["alpha"])


def horizon(cfg: dict, n_jobs: int) -> int:
    return max(cfg["horizon_min"], cfg["horizon_per_job"] * n_jobs)


def setup(run) -> dict:
    from repro.core import Job, ScheduleRequest, get_policy

    cfg, tf = run.config, run.traffic
    cluster = _cluster(cfg)
    base = traffic.philly_jobs(cfg["jobs"])
    pool = [traffic.permuted(base, run.seed, i) for i in range(tf["pool"])]
    requests = [ScheduleRequest(
        cluster=cluster, jobs=[Job(**j._asdict()) for j in member],
        horizon=horizon(cfg, len(member)), u=cfg["u"],
        params=dict(tf["params"])) for member in pool]
    policy = get_policy(tf["policy"])
    for req in requests:                       # compile and warm every shape
        policy(req)
    return {"pool": pool, "requests": requests, "policy": policy,
            "results": []}


def _counters() -> dict:
    from repro.core import contention
    from repro.kernels import placement
    return {"probes": contention.EVAL_COUNTS["probes"],
            **{f"dispatch_{k}": v for k, v in
               placement.DISPATCH_COUNTS.items()}}


def window(run, state) -> None:
    reqs, policy, results = state["requests"], state["policy"], \
        state["results"]
    before = _counters()
    t0 = time.perf_counter()
    i = 0
    while True:
        member = i % len(reqs)
        with run.span("replan"):
            results.append((member, policy(reqs[member])))
        i += 1
        if i % len(reqs) == 0 and time.perf_counter() - t0 >= run.seconds:
            break
    elapsed = time.perf_counter() - t0
    after = _counters()
    run.attempted = i
    run.e2e["replan_s"] = elapsed / i
    run.readings.update(replans=i, window_s=elapsed,
                        **{k: after[k] - before[k] for k in after})


def traced(run, state) -> None:
    with run.span("replan"):
        state["policy"](state["requests"][0])


def check(run, state) -> None:
    cfg = run.config
    refs = [sched_ref.sjf_bco(cfg["cluster"], member,
                              horizon(cfg, len(member)), cfg["u"])
            for member in state["pool"]]
    differing = 0
    for k, (member, got) in enumerate(state["results"]):
        bad = sched_ref.schedule_diff(got, refs[member])
        if bad:
            differing += 1
            print(f"replan {k} (pool member {member}) differs from the "
                  f"reference in {bad}", file=sys.stderr)
    run.failed = differing
    run.check("replans_differing", differing,
              run.limits["replans_differing"])

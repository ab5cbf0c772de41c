"""The re-plan cells' comparison: the plain SJF-BCO reference equals the
scheduler, its float32 control does not, an altered answer turns
``correct`` false, and the per-layer counters read one re-plan's deltas."""
from __future__ import annotations

import json
import time

import numpy as np
import pytest

from conftest import ROOT, TINY_CLUSTER

from bench import harness
from bench.drivers import replan
from bench.lib import sched_ref, traffic

CFG = json.loads((ROOT / "bench/configs/philly-20srv.json").read_text())


def _tiny_cfg():
    cfg = json.loads(json.dumps(CFG))
    cfg.update(cluster=TINY_CLUSTER, horizon_min=200)
    cfg["jobs"]["mix"] = [[1, 8], [2, 4], [4, 4], [8, 2]]
    return cfg


def _program(cfg, jobs, params):
    from repro.core import Job, ScheduleRequest, get_policy
    req = ScheduleRequest(cluster=replan._cluster(cfg),
                          jobs=[Job(**j._asdict()) for j in jobs],
                          horizon=replan.horizon(cfg, len(jobs)),
                          u=cfg["u"], params=params)
    return get_policy("sjf-bco")(req)


@pytest.mark.parametrize("params", [{}, {"placement": "columnar"}],
                         ids=["scalar", "columnar"])
@pytest.mark.parametrize("seed", [0, 3, 2 ** 31 + 11])
def test_reference_equals_the_scheduler(seed, params):
    cfg = _tiny_cfg()
    jobs = traffic.permuted(traffic.philly_jobs(cfg["jobs"]), seed, 0)
    got = _program(cfg, jobs, params)
    ref = sched_ref.sjf_bco(cfg["cluster"], jobs,
                            replan.horizon(cfg, len(jobs)), cfg["u"])
    assert sched_ref.schedule_diff(got, ref) == []


def test_reference_equals_the_scheduler_at_full_size():
    jobs = traffic.permuted(traffic.philly_jobs(CFG["jobs"]), 5, 1)
    got = _program(CFG, jobs, {})
    ref = sched_ref.sjf_bco(CFG["cluster"], jobs,
                            replan.horizon(CFG, len(jobs)), CFG["u"])
    assert len(jobs) == 160 and sum(CFG["cluster"]["capacities"]) == 336
    assert sched_ref.schedule_diff(got, ref) == []


def test_float32_control_is_rejected():
    """The reference in float32, put in the program's place, differs on
    the busy-time certificate (and often the assignment) on every seed of
    the full-size cell tried here."""
    base = traffic.philly_jobs(CFG["jobs"])
    for seed in (1, 2, 3):
        jobs = traffic.permuted(base, seed, 0)
        h = replan.horizon(CFG, len(jobs))
        ref = sched_ref.sjf_bco(CFG["cluster"], jobs, h, CFG["u"])
        ctl = sched_ref.sjf_bco(CFG["cluster"], jobs, h, CFG["u"],
                                np.float32)
        assert sched_ref.schedule_diff(ctl, ref), seed


def _run(tree, monkeypatch, alter=None):
    import repro.core
    if alter is not None:
        real = repro.core.get_policy

        def get_policy(name):
            policy = real(name)
            return lambda req: alter(policy(req))

        monkeypatch.setattr(repro.core, "get_policy", get_policy)
    return harness.run_cell("tiny.replan", 11, 0.2, False,
                            time.perf_counter(), bench_json=tree,
                            require_tpu=False)


def test_sound_run_is_correct(tree, monkeypatch):
    out = _run(tree, monkeypatch)
    assert out["correct"] and out["failed"] == 0


def _swap_gpu(res):
    jid, gpus = res.assignment[0]
    gpus = np.asarray(gpus).copy()
    gpus[0] = (int(gpus[0]) + 1) % 40
    res.assignment = [(jid, gpus)] + list(res.assignment[1:])
    return res


def _late_finish(res):
    res.est_finish = np.asarray(res.est_finish) + 1.0
    return res


@pytest.mark.parametrize("alter", [_swap_gpu, _late_finish],
                         ids=["gpu", "finish"])
def test_altered_answer_is_not_correct(tree, monkeypatch, alter):
    out = _run(tree, monkeypatch, alter)
    assert not out["correct"]
    assert out["checks"]["replans_differing"]["value"] == out["attempted"]
    assert out["failed"] == out["attempted"]


def test_counters_read_one_replan(monkeypatch):
    """probes / device calls / re-checked rows are deltas of the program's
    counters over the window, per re-plan."""
    from repro.core import contention
    from repro.kernels import placement

    monkeypatch.setattr(placement, "DISPATCH_MIN_ROWS", 0)
    cfg = _tiny_cfg()
    jobs = traffic.permuted(traffic.philly_jobs(cfg["jobs"]), 4, 0)
    p0 = contention.EVAL_COUNTS["probes"]
    d0 = dict(placement.DISPATCH_COUNTS)
    _program(cfg, jobs, {"placement": "columnar"})
    want_probes = contention.EVAL_COUNTS["probes"] - p0
    want_calls = placement.DISPATCH_COUNTS["device"] - d0["device"]
    assert want_calls > 0

    class FakeRun:
        readings: dict = {}
        e2e: dict = {}
        seconds = 0.0
        attempted = 0

        def span(self, name):
            import contextlib
            return contextlib.nullcontext()

    run = FakeRun()
    state = {"requests": [None], "results": [],
             "policy": lambda _req: _program(cfg, jobs,
                                             {"placement": "columnar"})}
    replan.window(run, state)
    r = run.readings
    assert r["replans"] == 1
    assert harness.read_metric("probes_per_replan", r) == want_probes
    assert harness.read_metric("device_calls_per_replan", r) == want_calls
    assert harness.read_metric("recheck_pct", r) == pytest.approx(
        100.0 * r["dispatch_rechecked"] / r["dispatch_rows"])

"""Heterogeneity-aware cluster model: degenerate bit-identity + directed
behaviour tests.

Two obligations from the per-GPU-speed / per-link-class refactor:

  * **Degenerate identity** -- a cluster whose ``gpu_speeds`` / ``links``
    arrays merely restate the homogeneous scalars must produce
    bit-identical results to the scalar cluster across every oracle axis:
    engines (incremental / batched / reference), sweep and bisect modes,
    placement engines (scalar / columnar), simulator readiness and
    stepping modes, and online arrivals.
  * **Directed heterogeneity** -- a genuinely mixed cluster must *change*
    behaviour the way Eqs. (1) and (6)-(8) say: a slow GPU tier flips
    SJF-BCO's placement away from the slow server, and an ``isolated``
    uplink drops the Eq. (8) sharing divisor ``f(alpha, k)``.

A hypothesis property sweep runs when hypothesis is installed (the CI
image may not ship it; the seeded numpy sweeps cover the same space
deterministically either way).
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import (Cluster, ClusterSpec, Job, ScheduleRequest,
                        evaluate, evaluate_many, get_policy, philly_cluster,
                        philly_workload, simulate, tau_bounds)
from repro.core.contention import IncrementalEval

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                 # pragma: no cover
    HAVE_HYPOTHESIS = False


def _uniform_hetero(cluster):
    """Restate a scalar cluster's constants as per-device arrays."""
    return dataclasses.replace(
        cluster,
        gpu_speeds=(cluster.gpu_speed,) * cluster.num_gpus,
        links=((cluster.b_inter, "shared"),) * cluster.num_servers)


def _philly_case(seed, n_jobs=42, n_servers=8):
    cluster = philly_cluster(n_servers, seed=seed)
    mix = ((1, n_jobs // 3), (2, n_jobs // 6), (4, n_jobs // 4),
           (8, n_jobs // 6), (16, n_jobs // 12))
    jobs = philly_workload(seed=seed, mix=mix)
    return cluster, jobs


def _hetero_case(seed, n_jobs=24, n_servers=6):
    """A genuinely mixed cluster (two speed tiers, mixed link classes)."""
    base = philly_cluster(n_servers, seed=seed)
    rng = np.random.default_rng(1000 + seed)
    speeds = []
    for cap in base.capacities:
        tier = float(rng.choice([base.gpu_speed, base.gpu_speed * 0.25]))
        speeds += [tier] * cap
    links = tuple(
        (float(rng.choice([base.b_inter, base.b_inter * 0.5])),
         str(rng.choice(["shared", "isolated"])))
        for _ in range(base.num_servers))
    cluster = dataclasses.replace(base, gpu_speeds=tuple(speeds),
                                  links=links)
    assert cluster.is_heterogeneous
    mix = ((1, n_jobs // 3), (2, n_jobs // 4), (4, n_jobs // 4),
           (8, n_jobs // 6))
    return cluster, philly_workload(seed=seed, mix=mix)


def _random_stack(cluster, jobs, rng, n_cands=5):
    S = cluster.num_servers
    stack = np.zeros((n_cands, len(jobs), S), dtype=np.int64)
    for c in range(n_cands):
        for i, job in enumerate(jobs):
            for _ in range(job.num_gpus):
                stack[c, i, rng.integers(S)] += 1
    return stack


def _feasible_stack(cluster, jobs, rng, n_cands=5):
    """Random placements that respect every server's capacity (jobs that
    no longer fit are left inactive) -> (stack, active)."""
    caps = np.asarray(cluster.capacities)
    stack = np.zeros((n_cands, len(jobs), cluster.num_servers),
                     dtype=np.int64)
    active = np.zeros((n_cands, len(jobs)), dtype=bool)
    for c in range(n_cands):
        free = caps.copy()
        for i, job in enumerate(jobs):
            if job.num_gpus > free.sum():
                continue
            for _ in range(job.num_gpus):
                s = rng.choice(np.flatnonzero(free > 0))
                stack[c, i, s] += 1
                free[s] -= 1
            active[c, i] = True
    return stack, active


def _assert_schedules_equal(a, b):
    assert a.theta == b.theta
    assert a.kappa == b.kappa
    assert a.est_makespan == b.est_makespan
    assert a.max_busy_time == b.max_busy_time
    assert len(a.assignment) == len(b.assignment)
    for (j1, g1), (j2, g2) in zip(a.assignment, b.assignment):
        assert j1 == j2
        assert np.array_equal(g1, g2)
    assert np.array_equal(a.est_start, b.est_start)
    assert np.array_equal(a.est_finish, b.est_finish)


def _assert_sims_equal(a, b):
    assert a.events == b.events
    assert np.array_equal(a.start, b.start)
    assert np.array_equal(a.finish, b.finish)
    assert a.makespan == b.makespan
    assert a.peak_contention == b.peak_contention


class TestClusterSurface:
    def test_uniform_arrays_are_degenerate(self):
        cluster = philly_cluster(4, seed=0)
        assert not cluster.is_heterogeneous
        assert not _uniform_hetero(cluster).is_heterogeneous

    def test_mixed_arrays_are_heterogeneous(self):
        cluster = philly_cluster(2, seed=0)
        speeds = list(_uniform_hetero(cluster).gpu_speeds)
        speeds[0] *= 0.5
        assert dataclasses.replace(
            cluster, gpu_speeds=tuple(speeds)).is_heterogeneous
        # An isolated link at the nominal bandwidth is still heterogeneous:
        # the class changes Eq. (8) even when the number doesn't.
        links = ((cluster.b_inter, "isolated"),) \
            + ((cluster.b_inter, "shared"),) * (cluster.num_servers - 1)
        assert dataclasses.replace(cluster, links=links).is_heterogeneous

    def test_derived_arrays(self):
        cluster = Cluster((2, 3), gpu_speeds=(50.0, 40.0, 50.0, 50.0, 10.0),
                          links=((1.25, "shared"), (0.5, "isolated")))
        assert np.array_equal(cluster.server_speed_floor, [40.0, 10.0])
        assert np.array_equal(cluster.uplink_bandwidth, [1.25, 0.5])
        assert np.array_equal(cluster.uplink_isolated, [False, True])
        assert np.array_equal(cluster.uplink_shared_or_inf, [1.25, np.inf])
        assert np.array_equal(cluster.uplink_isolated_or_inf, [np.inf, 0.5])

    @pytest.mark.parametrize("kwargs,match", [
        (dict(gpu_speeds=(50.0,)), "one speed per GPU"),
        (dict(gpu_speeds=50.0), "per-GPU"),
        (dict(gpu_speeds=(50.0, 50.0, 50.0, -1.0)), "positive"),
        (dict(links=((1.25, "shared"),)), "one uplink per server"),
        (dict(links=((1.25, "dedicated"), (1.25, "shared"))), "kind"),
        (dict(links=((0.0, "shared"), (1.25, "shared"))), "positive"),
        (dict(links=((500.0, "shared"), (1.25, "shared"))), "b_intra"),
        (dict(gpu_speed=(50.0, 50.0, 50.0, 50.0)), "gpu_speeds"),
        (dict(b_inter=(1.25, 1.25)), "links"),
    ])
    def test_loud_validation(self, kwargs, match):
        with pytest.raises((ValueError, TypeError), match=match):
            Cluster((2, 2), **kwargs)

    def test_payload_roundtrip(self):
        cluster, _ = _hetero_case(0)
        payload = json.loads(json.dumps(cluster.to_payload()))
        assert Cluster.from_payload(payload) == cluster
        scalar = philly_cluster(3, seed=1)
        assert Cluster.from_payload(
            json.loads(json.dumps(scalar.to_payload()))) == scalar

    def test_cluster_spec_draws_tiers(self):
        spec = ClusterSpec(num_servers=5, seed=3,
                           speed_tiers=((50.0, 0.5), (12.5, 0.5)),
                           link_classes=((1.25, "shared", 0.5),
                                         (1.25, "isolated", 0.5)))
        cluster = spec.build()
        assert cluster.is_heterogeneous
        assert set(cluster.gpu_speeds) <= {50.0, 12.5}
        # The capacity draw precedes the tier draws: same seed, same shape.
        assert cluster.capacities == philly_cluster(5, seed=3).capacities
        # A single tier restating the scalar is degenerate.
        assert not ClusterSpec(num_servers=5, seed=3,
                               speed_tiers=((50.0, 1.0),)).build() \
            .is_heterogeneous

    def test_unknown_override_rejected(self):
        spec = ClusterSpec(num_servers=2, overrides=(("gpu_speedz", 1.0),))
        with pytest.raises(ValueError, match="gpu_speedz.*speed_tiers"):
            spec.build()


class TestDegenerateIdentity:
    """Uniform hetero arrays == homogeneous scalars, bit for bit."""

    @pytest.mark.parametrize("policy", ["sjf-bco", "ff", "ls"])
    @pytest.mark.parametrize("seed", range(2))
    def test_policies(self, policy, seed):
        cluster, jobs = _philly_case(seed)
        a = get_policy(policy)(ScheduleRequest(cluster=cluster, jobs=jobs,
                                               horizon=2400))
        b = get_policy(policy)(ScheduleRequest(
            cluster=_uniform_hetero(cluster), jobs=jobs, horizon=2400))
        _assert_schedules_equal(a, b)

    @pytest.mark.parametrize("params", [
        {"engine": "incremental"},
        {"engine": "batched"},
        {"engine": "reference"},
        {"sweep": "sequential"},
        {"bisect": "sequential"},
        {"placement": "columnar"},
    ])
    def test_oracle_axes(self, params):
        cluster, jobs = _philly_case(1)
        a = get_policy("sjf-bco")(ScheduleRequest(
            cluster=cluster, jobs=jobs, horizon=2400, params=params))
        b = get_policy("sjf-bco")(ScheduleRequest(
            cluster=_uniform_hetero(cluster), jobs=jobs, horizon=2400,
            params=params))
        _assert_schedules_equal(a, b)

    @pytest.mark.parametrize("readiness,stepping,engine", [
        ("tracked", "multi", "incremental"),
        ("tracked", "single", "incremental"),
        ("rescan", None, "incremental"),
        ("tracked", None, "reference"),
    ])
    def test_simulator_axes(self, readiness, stepping, engine):
        cluster, jobs = _philly_case(2)
        uniform = _uniform_hetero(cluster)
        sched = get_policy("sjf-bco")(ScheduleRequest(cluster=cluster,
                                                      jobs=jobs,
                                                      horizon=2400))
        a = simulate(cluster, jobs, sched.assignment, engine=engine,
                     readiness=readiness, stepping=stepping)
        b = simulate(uniform, jobs, sched.assignment, engine=engine,
                     readiness=readiness, stepping=stepping)
        _assert_sims_equal(a, b)

    def test_online_arrivals(self):
        cluster, jobs = _philly_case(3, n_jobs=30)
        rng = np.random.default_rng(7)
        arrivals = rng.integers(0, 300, size=len(jobs)).astype(np.int64)
        req = dict(jobs=jobs, arrivals=arrivals, horizon=10**6)
        a = get_policy("sjf-bco")(ScheduleRequest(cluster=cluster, **req))
        b = get_policy("sjf-bco")(ScheduleRequest(
            cluster=_uniform_hetero(cluster), **req))
        _assert_schedules_equal(a, b)
        _assert_sims_equal(
            simulate(cluster, jobs, a.assignment, arrivals=arrivals),
            simulate(_uniform_hetero(cluster), jobs, b.assignment,
                     arrivals=arrivals))

    def test_engine_values_identical(self):
        cluster, jobs = _philly_case(4, n_jobs=18)
        uniform = _uniform_hetero(cluster)
        stack = _random_stack(cluster, jobs, np.random.default_rng(4))
        a, b = evaluate_many(cluster, jobs, stack), \
            evaluate_many(uniform, jobs, stack)
        assert np.array_equal(a.tau, b.tau)
        assert np.array_equal(a.bandwidth, b.bandwidth)
        assert np.array_equal(a.reduce, b.reduce)
        for job in jobs:
            assert tau_bounds(cluster, job) == tau_bounds(uniform, job)


def _engine_agreement(seed):
    """evaluate == evaluate_many == IncrementalEval on a mixed cluster."""
    cluster, jobs = _hetero_case(seed)
    rng = np.random.default_rng(seed)
    stack = _random_stack(cluster, jobs, rng)
    many = evaluate_many(cluster, jobs, stack)
    for c in range(stack.shape[0]):
        ref = evaluate(cluster, jobs, stack[c])
        assert np.array_equal(ref.tau, many.tau[c])
        assert np.array_equal(ref.bandwidth, many.bandwidth[c])
        inc = IncrementalEval(cluster)
        rows = [inc.add(job, stack[c, i]) for i, job in enumerate(jobs)]
        for i, r in enumerate(rows):
            assert inc.tau_of(r) == ref.tau[i]
        # Probes agree with committed rows.
        probe = inc.probe_tau_many(jobs[0], stack[:, 0, :])
        assert probe.shape == (stack.shape[0],)
    # tau_bounds brackets every realised tau of a placement that fits the
    # mixed cluster (its k_max premise: no more jobs share a server than
    # it has GPUs; the stack above ignores capacities).
    fstack, active = _feasible_stack(cluster, jobs, rng)
    fmany = evaluate_many(cluster, jobs, fstack, active=active)
    for i, job in enumerate(jobs):
        lo, hi = tau_bounds(cluster, job)
        taus = fmany.tau[active[:, i], i]
        assert np.all(taus >= lo) and np.all(taus <= hi)
    assert active.any()


class TestHeteroEngineAgreement:
    if HAVE_HYPOTHESIS:
        @given(st.integers(0, 2**31 - 1))
        @settings(max_examples=10, deadline=None)
        def test_engines_agree(self, seed):
            _engine_agreement(seed)
    else:
        @pytest.mark.parametrize("seed", [0, 1, 7, 23, 2**31 - 1])
        def test_engines_agree(self, seed):
            _engine_agreement(seed)

    def test_probe_matches_fresh_evaluate(self):
        """Hetero probes (scalar_tau fast path) == committing the row."""
        cluster, jobs = _hetero_case(2)
        rng = np.random.default_rng(2)
        placed = _random_stack(cluster, jobs[1:], rng, n_cands=1)[0]
        inc = IncrementalEval(cluster)
        for i, job in enumerate(jobs[1:]):
            inc.add(job, placed[i])
        cands = _random_stack(cluster, [jobs[0]], rng, n_cands=6)[:, 0, :]
        taus = inc.probe_tau_many(jobs[0], cands)
        for c in range(cands.shape[0]):
            assert taus[c] == inc.probe_tau(jobs[0], cands[c])

    def test_kernel_backend_agrees_x64(self):
        """The int32 kernel + float64 host terms equal the NumPy engine
        on a mixed cluster (x64 off, as the kernels run)."""
        pytest.importorskip("jax")
        from repro.core.contention import tau_backend
        cluster, jobs = _hetero_case(3, n_jobs=12)
        stack = _random_stack(cluster, jobs, np.random.default_rng(3))
        ref = evaluate_many(cluster, jobs, stack)
        with tau_backend("kernel"):
            kern = evaluate_many(cluster, jobs, stack)
        assert np.array_equal(ref.p, kern.p)
        assert np.array_equal(ref.tau, kern.tau)
        assert np.array_equal(ref.phi, kern.phi)
        assert np.array_equal(ref.bandwidth, kern.bandwidth)


class TestDirectedHetero:
    """Mixed clusters must change behaviour the way the model says."""

    def _straddle_case(self, links):
        cluster = Cluster((2, 2), links=links)
        jobs = [Job(jid=j, num_gpus=2, iters=3000, grad_size=1.5e-3,
                    batch=32, dt_fwd=3e-4, dt_bwd=8e-3) for j in range(2)]
        Y = np.array([[1, 1], [1, 1]], dtype=np.int64)   # both straddle
        return cluster, jobs, Y

    def test_isolated_uplink_drops_divisor(self):
        shared = ((1.25, "shared"), (1.25, "shared"))
        isolated = ((1.25, "isolated"), (1.25, "isolated"))
        cl_sh, jobs, Y = self._straddle_case(shared)
        cl_iso, _, _ = self._straddle_case(isolated)
        m_sh, m_iso = evaluate(cl_sh, jobs, Y), evaluate(cl_iso, jobs, Y)
        # Both jobs straddle both servers: p = 2, so f(alpha, k) > 1.
        assert np.array_equal(m_sh.p, [2, 2])
        k = max(cl_sh.xi1 * 2.0, 1.0)
        f = k + cl_sh.alpha * (k - 1.0)
        assert f > 1.0
        share = (jobs[0].grad_size / 2.0) * 1.0
        compute = jobs[0].dt_fwd * jobs[0].batch + jobs[0].dt_bwd
        # Shared uplinks pay the divisor; isolated uplinks do not (Eq. 8).
        assert np.array_equal(m_sh.bandwidth, [1.25 / f, 1.25 / f])
        assert np.array_equal(m_iso.bandwidth, [1.25, 1.25])
        expect_iso = 2.0 * share / 1.25 + share / cl_iso.gpu_speed \
            + cl_iso.xi2 * 2.0 + compute
        assert m_iso.tau[0] == expect_iso
        assert m_iso.tau[0] < m_sh.tau[0]

    def test_mixed_links_take_min(self):
        # One isolated uplink slower than shared/f: the isolated pipe caps.
        f_links = ((0.2, "isolated"), (1.25, "shared"))
        cluster, jobs, Y = self._straddle_case(f_links)
        model = evaluate(cluster, jobs, Y)
        k = max(cluster.xi1 * 2.0, 1.0)
        f = k + cluster.alpha * (k - 1.0)
        assert np.array_equal(model.bandwidth,
                              [min(0.2, 1.25 / f)] * 2)

    def test_slow_server_governs_reduce(self):
        cluster = Cluster((2, 2), gpu_speeds=(50.0, 50.0, 5.0, 5.0))
        job = Job(jid=0, num_gpus=2, iters=1000, grad_size=2e-3, batch=32,
                  dt_fwd=3e-4, dt_bwd=8e-3)
        fast = evaluate(cluster, [job], np.array([[2, 0]]))
        straddle = evaluate(cluster, [job], np.array([[1, 1]]))
        share = job.grad_size / 2.0
        assert fast.reduce[0] == share / 50.0
        assert straddle.reduce[0] == share / 5.0      # slowest member

    def test_slow_tier_flips_sjf_bco_placement(self):
        """A 20x-slower server visibly changes SJF-BCO's picks: the
        speed-aware schedule loads the fast server harder."""
        rng = np.random.default_rng(0)
        homog = Cluster((4, 4))
        slow = dataclasses.replace(
            homog,
            gpu_speeds=(homog.gpu_speed,) * 4
            + (homog.gpu_speed * 0.05,) * 4)
        jobs = [Job(jid=j, num_gpus=2,
                    iters=int(rng.integers(2000, 6000)),
                    grad_size=float(rng.uniform(1.5e-3, 2.0e-3)),
                    batch=int(rng.integers(16, 64)),
                    dt_fwd=float(rng.uniform(2e-4, 5e-4)),
                    dt_bwd=float(rng.uniform(4e-3, 1.2e-2)))
                for j in range(6)]
        sh = get_policy("sjf-bco")(ScheduleRequest(cluster=homog, jobs=jobs,
                                                   horizon=10**6))
        ss = get_policy("sjf-bco")(ScheduleRequest(cluster=slow, jobs=jobs,
                                                   horizon=10**6))
        counts = {}
        for name, cl, sched in (("homog", homog, sh), ("slow", slow, ss)):
            per = np.zeros(2, dtype=int)
            for _, gpus in sched.assignment:
                for g in gpus:
                    per[0 if g < 4 else 1] += 1
            counts[name] = per
        assert not np.array_equal(counts["homog"], counts["slow"])
        # Speed-aware placement shifts GPU-slots toward the fast server.
        assert counts["slow"][0] > counts["slow"][1]
        assert counts["slow"][1] < counts["homog"][1]

    def test_columnar_matches_scalar_on_hetero(self):
        cluster, jobs = _hetero_case(5, n_jobs=16)
        a = get_policy("sjf-bco")(ScheduleRequest(
            cluster=cluster, jobs=jobs, horizon=2400,
            params={"placement": "scalar"}))
        b = get_policy("sjf-bco")(ScheduleRequest(
            cluster=cluster, jobs=jobs, horizon=2400,
            params={"placement": "columnar"}))
        _assert_schedules_equal(a, b)


class TestHeteroService:
    def test_journal_recovers_hetero_cluster(self):
        from repro.service import (Daemon, QueueManager, SchedulerService,
                                   SubmitRequest, TenantConfig)

        cluster, jobs = _hetero_case(6, n_jobs=12)
        svc = SchedulerService(cluster, policy="sjf-bco")
        for i, job in enumerate(jobs):
            svc.submit(SubmitRequest(job, arrival=2 * i))
        while svc.step():
            pass
        live = svc.daemon
        # The journal's first record is the cluster itself...
        first = live.store.entries()[0]
        assert first.kind == "cluster"
        # ...so recovery needs no out-of-band cluster object.
        recovered = Daemon.recover(None, live.store,
                                   QueueManager(TenantConfig("sjf-bco")))
        assert recovered.cluster == cluster
        assert recovered.cluster.is_heterogeneous
        assert np.array_equal(live.state.U, recovered.state.U)

    def test_recover_rejects_mismatched_cluster(self):
        from repro.service import (Daemon, QueueManager, SchedulerService,
                                   TenantConfig)

        cluster, _ = _hetero_case(7, n_jobs=4)
        svc = SchedulerService(cluster, policy="sjf-bco")
        other = philly_cluster(2, seed=9)
        with pytest.raises(ValueError, match="cluster"):
            Daemon.recover(other, svc.daemon.store,
                           QueueManager(TenantConfig("sjf-bco")))

"""Columnar branch-vectorised placement vs the scalar oracle: the same
decisions, hence bit-equal schedules.

Mirrors ``tests/test_bisect_equivalence.py`` for the ``placement`` axis:
the :class:`~repro.core.columnar.ColumnarPlacement` engine must reproduce
the per-branch scalar walk decision-for-decision --

  * at the engine level: random clusters / jobs / theta ladders, every
    branch's survival, busy-time clocks, assignment and committed floats
    against an independent per-branch :func:`try_place` walk;
  * at the policy level: ``placement="columnar"`` vs ``"scalar"`` ends on
    the same (theta, kappa) and bit-equal schedules across policies,
    engines and bisect modes;
  * trivially for the policies with no columnar path (adaptive / rand /
    reserved): the param validates and both values coincide.

A hypothesis property sweep runs when hypothesis is installed (the CI
image may not ship it; the seeded numpy sweep below covers the same
space deterministically either way).
"""
import numpy as np
import pytest

from repro.core import (Cluster, Job, ScheduleRequest, get_policy,
                        philly_cluster, philly_workload)
from repro.core.api import (ColumnarPlacement, PlacementState, finalize,
                            nominal_rho, try_place)
from repro.core.sjf_bco import fa_ffp, lbsgf

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                 # pragma: no cover
    HAVE_HYPOTHESIS = False


def _philly_case(seed, n_jobs=42, n_servers=8):
    cluster = philly_cluster(n_servers, seed=seed)
    mix = ((1, n_jobs // 3), (2, n_jobs // 6), (4, n_jobs // 4),
           (8, n_jobs // 6), (16, n_jobs // 12))
    jobs = philly_workload(seed=seed, mix=mix)
    return cluster, jobs


def _random_case(rng, max_servers=6):
    """A small random cluster + workload + theta ladder + kappa split."""
    caps = rng.choice([4, 8, 16], size=rng.integers(2, max_servers + 1))
    cluster = Cluster(tuple(int(c) for c in caps))
    n = int(rng.integers(4, 14))
    jobs = [Job(jid=j,
                num_gpus=int(rng.integers(1, min(cluster.num_gpus, 16) + 1)),
                iters=int(rng.integers(200, 4000)),
                grad_size=float(rng.uniform(0.5e-3, 2.0e-3)),
                batch=int(rng.integers(16, 64)),
                dt_fwd=float(rng.uniform(2.0e-4, 5.0e-4)),
                dt_bwd=float(rng.uniform(4.0e-3, 1.2e-2)))
            for j in range(n)]
    u = float(rng.uniform(1.0, 4.0))
    rho_noms = {j.jid: nominal_rho(cluster, j) for j in jobs}
    floor = max(rho_noms.values()) / u
    # An ascending ladder straddling the feasibility boundary: some
    # branches should die, some survive.
    thetas = sorted(float(floor * f)
                    for f in rng.uniform(0.3, 40.0, size=rng.integers(3, 9)))
    kappas = sorted({int(k) for k in
                     rng.choice([1, 2, 4, 8, 16], size=rng.integers(1, 4))})
    return cluster, jobs, u, rho_noms, thetas, kappas


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


def _check_columnar_vs_scalar_walk(cluster, jobs, u, rho_noms, thetas,
                                   kappas, engine):
    """Drive one ColumnarPlacement over the (theta, kappa) grid and an
    independent scalar try_place walk per branch; compare everything."""
    order = sorted(jobs, key=lambda j: (rho_noms[j.jid], j.jid))
    pairs = [(float(th), k) for th in thetas for k in kappas]
    col = ColumnarPlacement(cluster, [th for th, _ in pairs], jobs, u,
                            engine=engine)
    kappa_arr = np.asarray([k for _, k in pairs], dtype=np.int64)
    for job in order:
        picker_of = (job.num_gpus > kappa_arr).astype(np.int64)
        col.place(job, rho_noms[job.jid], (fa_ffp, lbsgf), picker_of)
        if not col.alive.any():
            break
    for b, (theta, kappa) in enumerate(pairs):
        state = PlacementState(cluster, engine=engine)
        ok = True
        for job in order:
            picker = fa_ffp if job.num_gpus <= kappa else lbsgf
            if not try_place(state, job, picker, rho_noms[job.jid], u,
                             theta):
                ok = False
                break
        assert bool(col.alive[b]) == ok, (b, theta, kappa)
        if not ok:
            assert col.result(b, theta, kappa, "x") is None
            continue
        row = int(col.row_of[b])
        assert np.array_equal(col.U[row], state.U), (b, theta, kappa)
        assert np.array_equal(col.R[row], state.R), (b, theta, kappa)
        _assert_schedules_equal(col.result(b, theta, kappa, "x"),
                                finalize(state, len(jobs), theta, kappa,
                                         "x"))


class TestColumnarEngineRandomSweep:
    """Random clusters / jobs / ladders, engine-level decision identity."""

    @pytest.mark.parametrize("seed", range(12))
    def test_random_case_matches_scalar_walk(self, seed):
        rng = np.random.default_rng(seed)
        cluster, jobs, u, rho_noms, thetas, kappas = _random_case(rng)
        engine = ("incremental", "batched", "reference")[seed % 3]
        _check_columnar_vs_scalar_walk(cluster, jobs, u, rho_noms, thetas,
                                       kappas, engine)


class TestColumnarPolicyEquivalence:
    @pytest.mark.parametrize("seed", range(2))
    @pytest.mark.parametrize("engine", ["incremental", "batched",
                                        "reference"])
    @pytest.mark.parametrize("bisect", ["speculative", "sequential"])
    def test_sjf_bco(self, seed, engine, bisect):
        cluster, jobs = _philly_case(seed)
        results = {}
        for placement in ("scalar", "columnar"):
            request = ScheduleRequest(
                cluster=cluster, jobs=jobs, horizon=2400,
                params={"engine": engine, "bisect": bisect,
                        "placement": placement})
            results[placement] = get_policy("sjf-bco")(request)
        _assert_schedules_equal(results["scalar"], results["columnar"])

    @pytest.mark.parametrize("seed", range(2))
    @pytest.mark.parametrize("policy", ["ff", "ls"])
    @pytest.mark.parametrize("bisect", ["speculative", "sequential"])
    def test_single_picker_policies(self, seed, policy, bisect):
        cluster, jobs = _philly_case(seed)
        results = {}
        for placement in ("scalar", "columnar"):
            request = ScheduleRequest(
                cluster=cluster, jobs=jobs, horizon=2400,
                params={"bisect": bisect, "placement": placement})
            results[placement] = get_policy(policy)(request)
        _assert_schedules_equal(results["scalar"], results["columnar"])

    @pytest.mark.parametrize("policy,params", [
        ("sjf-bco-adaptive", {}),
        ("rand", {"seed": 3}),
        ("reserved", {"reserved_fraction": 0.25}),
    ])
    def test_scalar_only_policies_accept_the_param(self, policy, params):
        """Policies with no columnar path still validate ``placement``
        and coincide trivially for both values."""
        cluster, jobs = _philly_case(1, n_jobs=24, n_servers=6)
        results = {}
        for placement in ("scalar", "columnar"):
            request = ScheduleRequest(
                cluster=cluster, jobs=jobs, horizon=2400,
                params={**params, "placement": placement})
            results[placement] = get_policy(policy)(request)
        _assert_schedules_equal(results["scalar"], results["columnar"])
        with pytest.raises(ValueError, match="placement"):
            get_policy(policy)(ScheduleRequest(
                cluster=cluster, jobs=jobs, horizon=2400,
                params={**params, "placement": "bogus"}))

    def test_warm_start_falls_back_to_scalar(self):
        """warm_start changes the search trajectory, so columnar must
        quietly fall back -- both placements give the warm result."""
        cluster, jobs = _philly_case(0, n_jobs=24, n_servers=6)
        results = {}
        for placement in ("scalar", "columnar"):
            request = ScheduleRequest(
                cluster=cluster, jobs=jobs, horizon=2400,
                params={"warm_start": True, "placement": placement})
            results[placement] = get_policy("sjf-bco")(request)
        _assert_schedules_equal(results["scalar"], results["columnar"])


class TestColumnarJitBackends:
    """The fused jit/Pallas backends (int32/float32 device programs with
    float64 host re-checks) vs the numpy walk: the same schedules across
    seeds x policies x hetero clusters, plus the no-retrace guard (the
    padded array program must not recompile as jobs stream through)."""

    @staticmethod
    def _force_device(monkeypatch):
        """Force every batch through the device program: without this the
        DISPATCH_MIN_ROWS gate routes short batches to the numpy pickers
        and the device path would go untested at test sizes."""
        import repro.kernels.placement as kp
        monkeypatch.setattr(kp, "DISPATCH_MIN_ROWS", 0)

    def _hetero_case(self, seed, n_jobs=24, n_servers=6):
        import dataclasses
        base = philly_cluster(n_servers, seed=seed)
        rng = np.random.default_rng(1000 + seed)
        speeds = []
        for cap in base.capacities:
            tier = float(rng.choice([base.gpu_speed, base.gpu_speed / 4]))
            speeds += [tier] * cap
        links = tuple(
            (float(rng.choice([base.b_inter, base.b_inter * 0.5])),
             str(rng.choice(["shared", "isolated"])))
            for _ in range(base.num_servers))
        cluster = dataclasses.replace(base, gpu_speeds=tuple(speeds),
                                      links=links)
        assert cluster.is_heterogeneous
        mix = ((1, n_jobs // 3), (2, n_jobs // 6), (4, n_jobs // 4),
               (8, n_jobs // 6), (16, n_jobs // 12))
        return cluster, philly_workload(seed=seed, mix=mix)

    @pytest.mark.parametrize("seed", range(2))
    @pytest.mark.parametrize("policy", ["sjf-bco", "ff", "ls"])
    @pytest.mark.parametrize("hetero", [False, True])
    def test_jit_vs_eager_bit_identity(self, seed, policy, hetero,
                                       monkeypatch):
        """backend="jit" (fused XLA program in float32 + host re-checks
        and rankings) makes the eager numpy walk's AND the scalar
        oracle's decisions: the schedules are equal bit-for-bit."""
        pytest.importorskip("jax")
        self._force_device(monkeypatch)
        if hetero:
            cluster, jobs = self._hetero_case(seed)
        else:
            cluster, jobs = _philly_case(seed, n_jobs=30, n_servers=6)
        results = {}
        for backend, placement in (("numpy", "columnar"),
                                   ("jit", "columnar"),
                                   ("numpy", "scalar")):
            request = ScheduleRequest(
                cluster=cluster, jobs=jobs, horizon=2400,
                params={"placement": placement,
                        "columnar_backend": backend})
            results[(backend, placement)] = get_policy(policy)(request)
        _assert_schedules_equal(results[("numpy", "columnar")],
                                results[("jit", "columnar")])
        _assert_schedules_equal(results[("numpy", "scalar")],
                                results[("jit", "columnar")])

    @pytest.mark.parametrize("seed,hetero", [(0, False), (1, True)])
    def test_kernel_vs_numpy_bit_identity(self, seed, hetero, monkeypatch):
        """backend="kernel" (Pallas pick/check/score, interpret mode on
        CPU, 32-bit) gives the numpy walk's schedule bit-for-bit."""
        pytest.importorskip("jax")
        self._force_device(monkeypatch)
        if hetero:
            cluster, jobs = self._hetero_case(seed, n_jobs=18)
        else:
            cluster, jobs = _philly_case(seed, n_jobs=18, n_servers=4)
        results = {}
        for backend in ("numpy", "kernel"):
            request = ScheduleRequest(
                cluster=cluster, jobs=jobs, horizon=2400,
                params={"placement": "columnar",
                        "columnar_backend": backend})
            results[backend] = get_policy("sjf-bco")(request)
        _assert_schedules_equal(results["numpy"], results["kernel"])

    def test_pick_orders_device_matches_numpy(self, monkeypatch):
        """Function-level fuzz: the fused pick/check program and the
        numpy fallback agree exactly on every output (clocks, pool
        counts, rankings, feasibility) across random clock states."""
        pytest.importorskip("jax")
        import repro.kernels.placement as kp
        cluster, jobs = _philly_case(5, n_jobs=12, n_servers=6)
        N = cluster.num_gpus
        rng = np.random.default_rng(11)
        for trial in range(40):
            job = jobs[int(rng.integers(len(jobs)))]
            nw = int(rng.integers(1, 40))
            U = np.round(rng.uniform(0, 30, size=(nw, N)), 3)
            th_lo = np.sort(rng.uniform(5, 40, size=nw))
            th_hi = th_lo + rng.uniform(0, 10, size=nw)
            rho_u = rng.uniform(0.5, 20, size=nw)
            pid = rng.integers(0, 2, size=nw)
            outs = {}
            for rows, label in ((10**9, "numpy"), (0, "device")):
                monkeypatch.setattr(kp, "DISPATCH_MIN_ROWS", rows)
                outs[label] = kp.pick_orders(
                    cluster, U.copy(), th_lo, th_hi, rho_u, pid, job)
            for a, b in zip(outs["numpy"], outs["device"]):
                assert np.array_equal(np.asarray(a), np.asarray(b)), \
                    f"trial {trial}"

    def test_no_retrace_across_jobs(self, monkeypatch):
        """Compile-count guard: the padded fixed-shape layout must hit
        the jit cache across jobs -- a fresh workload on the same
        cluster adds ZERO new compilations."""
        pytest.importorskip("jax")
        self._force_device(monkeypatch)
        import repro.kernels.placement as kp
        cold = dict(kp.compile_counts())    # cumulative across session
        cluster, jobs = self._hetero_case(7, n_jobs=36)
        request = ScheduleRequest(
            cluster=cluster, jobs=jobs, horizon=2400,
            params={"placement": "columnar", "columnar_backend": "jit"})
        get_policy("sjf-bco")(request)
        warm = dict(kp.compile_counts())
        # A padded program per power-of-two row bucket and static-arg
        # combination -- not per job, not per branch count.  Counts
        # are session-cumulative, so bound the delta from this run
        # (earlier warm cache entries make it smaller, never larger).
        assert warm["pick_orders"] - cold["pick_orders"] <= 16
        assert warm["score_probes"] - cold["score_probes"] <= 16
        assert warm["pick_orders"] > 0 and warm["score_probes"] > 0
        _, jobs2 = self._hetero_case(8, n_jobs=36)
        request2 = ScheduleRequest(
            cluster=cluster, jobs=jobs2, horizon=2400,
            params={"placement": "columnar", "columnar_backend": "jit"})
        get_policy("sjf-bco")(request2)
        assert kp.compile_counts() == warm      # no retraces


class TestFloat32Screens:
    """Adversarial near-ties for the 32-bit device programs (Pallas
    interpret mode, x64 off): values within one f32 ulp of an Eq. (16)
    threshold, equal and nearly equal server loads, and taus whose
    1/tau sits on an integer.  The screens must flag those rows and the
    float64 host re-check must hand back the oracle's decisions."""

    @staticmethod
    def _pick_both(monkeypatch, cluster, U, th_lo, th_hi, rho_u, pid, job):
        import repro.kernels.placement as kp
        monkeypatch.setattr(kp, "DISPATCH_MIN_ROWS", 10**9)
        ref = kp.pick_orders(cluster, U, th_lo, th_hi, rho_u, pid, job)
        before = kp.DISPATCH_COUNTS["rechecked"]
        dev = kp.pick_orders(cluster, U, th_lo, th_hi, rho_u, pid, job,
                             use_kernel=True)
        for a, b in zip(ref, dev):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        return kp.DISPATCH_COUNTS["rechecked"] - before

    @pytest.mark.parametrize("pid", [0, 1])
    def test_pool_threshold_within_one_ulp(self, pid, monkeypatch):
        pytest.importorskip("jax")
        cluster = Cluster((8, 8, 8, 8))
        job = Job(jid=0, num_gpus=4, iters=1000, grad_size=1e-3, batch=32,
                  dt_fwd=3e-4, dt_bwd=8e-3)
        rng = np.random.default_rng(pid)
        nw, N = 16, cluster.num_gpus
        U = np.round(rng.uniform(0, 50, size=(nw, N)), 2)
        rho_u = np.full(nw, 3.0)
        V = U + rho_u[:, None]
        # Each row's threshold sits a hair (far below one f32 ulp at
        # this magnitude) above, at or below one of its clocks.
        pick = V[np.arange(nw), rng.integers(N, size=nw)]
        th_lo = pick - 1e-9 + rng.choice([-1e-12, 0.0, 1e-12], size=nw)
        th_hi = th_lo + rng.choice([0.0, 1e-11], size=nw)
        flagged = self._pick_both(monkeypatch, cluster, U, th_lo, th_hi,
                                  rho_u, np.full(nw, pid), job)
        assert flagged == nw

    @pytest.mark.parametrize("pid", [0, 1])
    def test_equal_and_near_equal_loads(self, pid, monkeypatch):
        pytest.importorskip("jax")
        cluster = Cluster((4, 4, 4, 4, 4))
        job = Job(jid=0, num_gpus=2, iters=1000, grad_size=1e-3, batch=32,
                  dt_fwd=3e-4, dt_bwd=8e-3)
        nw, N = 12, cluster.num_gpus
        U = np.zeros((nw, N))
        # Rows 0-3: every server equally loaded (exact ties, nonzero);
        # rows 4-7: loads one part in 1e12 apart; rows 8-11: all idle.
        U[:4] = 7.25
        U[4:8] = 7.25 + np.arange(N)[None, :] // 4 * 1e-11
        th = np.full(nw, 1e6)
        flagged = self._pick_both(monkeypatch, cluster, U, th, th,
                                  np.full(nw, 1.5), np.full(nw, pid), job)
        assert flagged == 8            # the idle rows tie exactly in f32

    def test_ties_outside_lbsgf_prefix_not_rechecked(self, monkeypatch):
        """LBSGF ranks only its least-loaded prefix (capacity before it
        < lambda*G): equal loads on servers beyond that prefix leave the
        pick unchanged, so the screen lets the device keys through."""
        pytest.importorskip("jax")
        cluster = Cluster((4, 4, 4, 4, 4, 4))
        job = Job(jid=0, num_gpus=4, iters=1000, grad_size=1e-3, batch=32,
                  dt_fwd=3e-4, dt_bwd=8e-3)
        nw = 8
        # Server s holds load 10*(s+1) per GPU, except servers 4 and 5
        # tie exactly -- far behind the one-server prefix.
        per_srv = np.array([10.0, 20.0, 30.0, 40.0, 55.5, 55.5])
        U = np.tile(np.repeat(per_srv, 4), (nw, 1))
        th = np.full(nw, 1e6)
        flagged = self._pick_both(monkeypatch, cluster, U, th, th,
                                  np.full(nw, 1.5), np.full(nw, 1), job)
        assert flagged == 0
        U[:, 4:8] = 10.0 * (1 + 1e-12)        # now the prefix near-ties
        flagged = self._pick_both(monkeypatch, cluster, U, th, th,
                                  np.full(nw, 1.5), np.full(nw, 1), job)
        assert flagged == nw

    def test_phi_on_an_integer(self, monkeypatch):
        """Jobs whose single-server tau is 1/k to float64 rounding."""
        pytest.importorskip("jax")
        import repro.kernels.placement as kp
        from repro.core.contention import scalar_tau
        cluster = Cluster((8, 8, 8, 8))
        Y = np.zeros((8, 4), dtype=np.int64)
        Y[:4, 0] = 4                              # one server
        Y[4:, :2] = 2                             # two servers
        p = np.array([1, 1, 1, 1, 2, 2, 2, 2], dtype=np.float64)
        for k in (3, 37, 250):
            base = Job(jid=0, num_gpus=4, iters=999, grad_size=1e-3,
                       batch=1, dt_fwd=0.0, dt_bwd=0.0)
            rest = scalar_tau(cluster, base, 1, 1)
            job = Job(jid=0, num_gpus=4, iters=999, grad_size=1e-3,
                      batch=1, dt_fwd=0.0, dt_bwd=1.0 / k - rest)
            monkeypatch.setattr(kp, "DISPATCH_MIN_ROWS", 10**9)
            ref = kp.score_probes(cluster, job, Y, p)
            before = kp.DISPATCH_COUNTS["rechecked"]
            dev = kp.score_probes(cluster, job, Y, p, use_kernel=True)
            assert np.array_equal(ref, dev)
            assert kp.DISPATCH_COUNTS["rechecked"] - before >= 4

    @pytest.mark.parametrize("hetero", [False, True])
    def test_uniform_cluster_schedule(self, hetero, monkeypatch):
        """Policy level: identical servers and identical jobs make every
        load comparison a tie; the kernel path keeps the scalar oracle's
        schedule."""
        pytest.importorskip("jax")
        import dataclasses

        import repro.kernels.placement as kp
        monkeypatch.setattr(kp, "DISPATCH_MIN_ROWS", 0)
        cluster = Cluster((8,) * 6)
        if hetero:
            cluster = dataclasses.replace(
                cluster, gpu_speeds=tuple([cluster.gpu_speed] * 24
                                          + [cluster.gpu_speed / 2] * 24),
                links=tuple((cluster.b_inter, kind) for kind in
                            ("shared", "isolated") * 3))
        jobs = [Job(jid=j, num_gpus=(2, 4, 8)[j % 3], iters=1500,
                    grad_size=1e-3, batch=32, dt_fwd=3e-4, dt_bwd=8e-3)
                for j in range(18)]
        results = {}
        for placement, backend in (("scalar", "numpy"),
                                   ("columnar", "kernel")):
            request = ScheduleRequest(
                cluster=cluster, jobs=jobs, horizon=2400,
                params={"placement": placement,
                        "columnar_backend": backend})
            results[placement] = get_policy("sjf-bco")(request)
        _assert_schedules_equal(results["scalar"], results["columnar"])


if HAVE_HYPOTHESIS:                                 # pragma: no branch
    class TestColumnarHypothesis:
        @settings(max_examples=25, deadline=None)
        @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
        def test_property_random_sweep(self, seed):
            rng = np.random.default_rng(seed)
            cluster, jobs, u, rho_noms, thetas, kappas = _random_case(rng)
            engine = ("incremental", "batched", "reference")[seed % 3]
            _check_columnar_vs_scalar_walk(cluster, jobs, u, rho_noms,
                                           thetas, kappas, engine)

"""The program's host spans (``repro.spans``) and the bisection counter
(``repro.core.api.SEARCH_COUNTS``): spans are no-ops without JAX, name
exactly the documented list in a recorded trace, and change no schedule;
the counter counts the policy's bisection rounds."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import spans
from repro.core import Cluster, Job, ScheduleRequest, get_policy
from repro.core import api, sjf_bco

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NO_JAX = """
import sys
sys.modules["jax"] = None                   # `import jax` now fails
import repro.core as rc
from repro import spans
req = rc.ScheduleRequest(cluster=rc.Cluster((8, 4, 8)), horizon=400,
                         jobs=rc.philly_workload(seed=3, mix=((1, 4), (2, 3),
                                                              (4, 2))))
res = rc.get_policy("sjf-bco")(req)
assert res.assignment and rc.api.SEARCH_COUNTS["rounds"] > 0
assert spans.span(spans.SEARCH_ROUND, round=0) is spans._NOOP
with spans.span(spans.LAUNCH, rows=3):
    pass
assert sys.modules["jax"] is None
print("ok")
"""


def test_core_schedules_without_jax():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", NO_JAX], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


def _tiny_request(params):
    cluster = Cluster((8, 4, 8, 4, 16, 8))
    jobs = [Job(jid=j, num_gpus=(1, 2, 4, 8)[j % 4], iters=1200 + 37 * j,
                grad_size=1e-3, batch=32, dt_fwd=3e-4, dt_bwd=8e-3)
            for j in range(14)]
    return ScheduleRequest(cluster=cluster, jobs=jobs, horizon=2400,
                           params=params)


@pytest.mark.parametrize("params", [
    {}, {"placement": "columnar", "columnar_backend": "numpy"},
    {"bisect": "sequential"}], ids=["scalar", "columnar", "sequential"])
def test_search_counts_count_the_policy_rounds(params, monkeypatch):
    calls = {"rounds": 0}
    real = sjf_bco.bisect_theta

    def counted(fn):
        def run(*args):
            calls["rounds"] += 1
            return fn(*args)
        return run

    def bisect_theta(attempt, *args, attempt_many=None, **kw):
        return real(counted(attempt), *args,
                    attempt_many=attempt_many and counted(attempt_many),
                    **kw)

    monkeypatch.setattr(sjf_bco, "bisect_theta", bisect_theta)
    before = dict(api.SEARCH_COUNTS)
    get_policy("sjf-bco")(_tiny_request(params))
    got = {k: api.SEARCH_COUNTS[k] - before[k] for k in before}
    assert got == calls and calls["rounds"] > 1


def _uniform_hetero_request():
    """Identical servers and jobs make every load comparison a tie, so
    the device programs' screens send rows to the float64 re-check, and
    the heterogeneous speeds and uplinks route scoring through
    ``score_probes``: every span of the list opens."""
    cluster = Cluster((8,) * 6)
    cluster = dataclasses.replace(
        cluster, gpu_speeds=tuple([cluster.gpu_speed] * 24
                                  + [cluster.gpu_speed / 2] * 24),
        links=tuple((cluster.b_inter, kind)
                    for kind in ("shared", "isolated") * 3))
    jobs = [Job(jid=j, num_gpus=(2, 4, 8)[j % 3], iters=1500,
                grad_size=1e-3, batch=32, dt_fwd=3e-4, dt_bwd=8e-3)
            for j in range(12)]
    return ScheduleRequest(cluster=cluster, jobs=jobs, horizon=2400,
                           params={"placement": "columnar",
                                   "columnar_backend": "jit"})


def _program_spans(log_dir):
    from jax.profiler import ProfileData
    import glob
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name.startswith("repro.")]


def test_recorded_spans_are_the_documented_list(tmp_path, monkeypatch):
    """A traced re-plan opens exactly the spans of ``spans.NAMES`` and
    returns the same schedule as the untraced one."""
    import jax

    from repro.kernels import placement
    monkeypatch.setattr(placement, "DISPATCH_MIN_ROWS", 0)
    policy = get_policy("sjf-bco")
    request = _uniform_hetero_request()
    plain = policy(request)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = policy(request)
    finally:
        jax.profiler.stop_trace()
    seen = _program_spans(str(tmp_path))
    assert set(seen) == set(spans.NAMES)
    assert len(spans.NAMES) == len(set(spans.NAMES))
    # Every batch dispatched: one launch and one fetch per device call.
    calls = seen.count(spans.PICK_ORDERS) + seen.count(spans.SCORE_PROBES)
    assert seen.count(spans.LAUNCH) == seen.count(spans.FETCH) == calls
    assert (traced.theta, traced.kappa) == (plain.theta, plain.kappa)
    assert traced.max_busy_time == plain.max_busy_time
    assert [j for j, _ in traced.assignment] == \
        [j for j, _ in plain.assignment]
    for (_, a), (_, b) in zip(traced.assignment, plain.assignment):
        assert np.array_equal(a, b)
    assert np.array_equal(traced.est_start, plain.est_start)
    assert np.array_equal(traced.est_finish, plain.est_finish)

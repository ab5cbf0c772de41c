"""The span reduction (``bench.lib.spans``) and the per-layer readers
built on it: self time on hand-made nested spans, idle time put down to
the innermost span of either prefix, idle time under no program span, the
committed CPU traces, and the readers on a traced run of the tiny cell.

``data/replan_trace.xplane.pb`` is one re-plan of the tiny cell on the
columnar engine's jit backend, every batch dispatched, recorded on the
CPU backend by ``python tests/bench/test_bench_spans.py <log dir>``.
"""
from __future__ import annotations

import json
import shutil
import sys
import time

import pytest

from conftest import ROOT, TINY_CLUSTER

from bench import harness
from bench.lib import spans as sp
from bench.lib import trace as tr

REPLAN_TRACE = ROOT / "tests/bench/data/replan_trace.xplane.pb"
CPU_TRACE = ROOT / "tests/bench/data/cpu_trace.xplane.pb"
READERS = ("search_rounds_per_replan", "search_ms_per_replan",
           "walk_ms_per_replan", "kernel_host_ms_per_replan",
           "roundtrip_ms_per_replan", "unspanned_idle_pct.replan")


def _layers_ms(out):
    """The four layer times of the readers, summed over the stretch."""
    t = out["spans"]
    part = lambda names, kind: sum(t.get(n, {}).get(kind, 0.0)
                                   for n in names)
    return 1000.0 * (part(sp.SEARCH, "self_s") + part(sp.WALK, "self_s")
                     + part(sp.KERNEL_HOST, "self_s")
                     + part(sp.ROUNDTRIP, "total_s"))


def test_self_time_by_hand():
    """Line A: a round 0-13 holding steps 1-4 and 5-12.5, the second
    holding a launch 6-7 and a fetch 7-12.5; the window ends at 11, so
    the round, the second step and the fetch run past it.  Line B: a
    round 2-9 with a step 3-5, which is no child of line A's spans."""
    A, B = ("/host:CPU", 0), ("/host:CPU", 1)
    spans = [("repro.search.round", 0.0, 13.0, A),
             ("repro.columnar.place", 1.0, 4.0, A),
             ("repro.columnar.place", 5.0, 12.5, A),
             ("repro.placement.launch", 6.0, 7.0, A),
             ("repro.placement.fetch", 7.0, 12.5, A),
             ("repro.search.round", 2.0, 9.0, B),
             ("repro.columnar.place", 3.0, 5.0, B)]
    t = sp.span_table(spans, (0.5, 11.0))
    # Round A: 0.5-11 (10.5 s) less its steps 1-4 and 5-11: self 1.5 s.
    # Round B: 7 s less its 2 s step: self 5 s.
    assert t["repro.search.round"] == pytest.approx(
        {"count": 2, "total_s": 17.5, "self_s": 6.5})
    # Steps 3 + 6 + 2 s; the second's children take 6-7 and 7-11.
    assert t["repro.columnar.place"] == pytest.approx(
        {"count": 3, "total_s": 11.0, "self_s": 6.0})
    assert t["repro.placement.fetch"] == pytest.approx(
        {"count": 1, "total_s": 4.0, "self_s": 4.0})
    assert sp.span_table(spans, (13.5, 20.0)) == {}


def _events():
    """One device, busy 0-1, 4-5 and 9-10 of a 0-12 stretch."""
    A = ("/host:CPU", 0)
    return {"devices": {"/device:TPU:0": [("fusion.1", 0.0, 1.0),
                                          ("fusion.2", 4.0, 5.0),
                                          ("fusion.3", 9.0, 10.0)]},
            "spans": [("bench.traced", 0.0, 12.0, A),
                      ("bench.replan", 0.0, 11.0, A),
                      ("repro.search.round", 1.5, 10.5, A),
                      ("repro.columnar.place", 2.0, 3.5, A),
                      ("repro.placement.fetch", 5.0, 8.0, A)]}


def test_idle_gaps_go_to_the_innermost_span_of_either_prefix():
    out = sp.reduce(_events(), (0.0, 12.0))
    # Gaps 1-4 (mid 2.5: in place), 5-9 (mid 7: in fetch), 10-12 (mid
    # 11: in the replan, outside every program span).
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"repro.columnar.place": 3.0, "repro.placement.fetch": 4.0,
         "bench.replan": 2.0})
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["replans"] == 1


def test_idle_time_under_no_program_span():
    # Idle 1-4, 5-9, 10-12; the round covers 1.5-10.5: unspanned are
    # 1-1.5 and 10.5-12.
    assert sp.idle_unspanned_s(_events(), (0.0, 12.0)) == pytest.approx(2.0)
    assert sp.idle_unspanned_s(_events(), (2.0, 11.0)) == pytest.approx(0.5)


def test_committed_cpu_trace_reduces_as_before():
    """Over both prefixes the reduction keeps every number that
    ``reduce_trace`` gives over ``bench.`` spans alone."""
    old = tr.load_events(str(CPU_TRACE), cpu_ops=True)
    ev = sp.load(str(CPU_TRACE))
    window = sp.traced_window(ev)
    assert window == tr.span_window(old, "bench.traced")
    before = tr.reduce_trace(old, window)
    after = sp.reduce(ev, window)
    for key in ("window_s", "busy_s", "devices", "collective_s",
                "collective_exposed_s", "device_ops", "idle_gaps"):
        assert after[key] == before[key], key
    assert after["spans"]["bench.step"]["count"] == 3
    assert after["replans"] == 0


def test_committed_replan_trace_nests_and_covers():
    ev = sp.load(str(REPLAN_TRACE))
    t0, t1 = sp.traced_window(ev)
    program = [s for s in ev["spans"] if s[0].startswith("repro.")]
    assert {s[0] for s in program} >= {
        "repro.search.round", "repro.columnar.place",
        "repro.placement.pick_orders", "repro.placement.launch",
        "repro.placement.fetch", "repro.placement.rank"}
    assert all(t0 <= a <= b <= t1 for _, a, b, _ in program)
    out = sp.reduce(ev, (t0, t1))
    replan = out["spans"]["bench.replan"]
    assert out["replans"] == 1
    share = _layers_ms(out) / (1000.0 * replan["total_s"])
    assert 0.95 <= share <= 1.0
    assert out["idle_unspanned_s"] < 0.05 * out["window_s"]
    assert out["idle_gaps"][0][0].startswith("repro.")
    # Counts on the trace: one place span per step, one pick_orders, one
    # launch and one fetch per dispatched step.
    counts = {n: v["count"] for n, v in out["spans"].items()}
    assert counts["repro.placement.launch"] == \
        counts["repro.placement.fetch"] == \
        counts["repro.placement.pick_orders"]


@pytest.mark.parametrize("name", READERS)
def test_readers_read_none_without_a_trace(name):
    assert harness.read_metric(name, {}) is None
    assert harness.read_metric(name, {"trace": None, "replans": 4}) is None


def _put_trace(out_dir, src):
    """``src`` where the harness writes a cell's trace."""
    dst = out_dir / "trace" / "x.y" / "plugins" / "profile" / "1"
    dst.mkdir(parents=True)
    shutil.copy(src, dst / "t.xplane.pb")


@pytest.mark.parametrize("name", READERS)
def test_readers_read_none_from_a_program_without_spans(name, tmp_path,
                                                        monkeypatch):
    """The committed CPU trace holds ``bench.`` spans alone, as a traced
    run of a program without ``repro.`` spans does."""
    _put_trace(tmp_path, CPU_TRACE)
    monkeypatch.setattr(harness, "OUT", tmp_path)
    ev = sp.load(str(CPU_TRACE))
    t0, t1 = sp.traced_window(ev)
    r = {"trace": {"window_s": t1 - t0}, "replans": 4,
         "traffic": {"pool": 4}}
    assert harness.read_metric(name, r) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_ignore_another_runs_trace(name, tmp_path, monkeypatch):
    _put_trace(tmp_path, REPLAN_TRACE)
    monkeypatch.setattr(harness, "OUT", tmp_path)
    r = {"trace": {"window_s": 1.0}, "replans": 4, "traffic": {"pool": 4}}
    assert harness.read_metric(name, r) is None


def test_readers_on_a_traced_run_of_the_tiny_cell(tree, monkeypatch):
    """Every reader reads the traced re-plan; the four layer times cover
    it, and the rounds are the window's mean, counted anew here."""
    from repro.core import Job, ScheduleRequest, api, get_policy
    from repro.kernels import placement

    from bench.drivers import replan
    from bench.lib import traffic

    monkeypatch.setattr(placement, "DISPATCH_MIN_ROWS", 0)
    monkeypatch.setattr(harness, "OUT", tree.parent / harness.OUT.name)
    monkeypatch.setitem(api.SEARCH_COUNTS, "rounds", 0)
    seed = 2 ** 31 + 9
    out = harness.run_cell("tiny.replan", seed, 0.2, True,
                           time.perf_counter(), bench_json=tree,
                           require_tpu=False)
    assert out["correct"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(READERS) <= set(m)
    sp._CACHE.clear()
    run = sp.traced_run({"trace": {"window_s": out["device"]["window_s"]}})
    replan_ms = 1000.0 * run["spans"]["bench.replan"]["total_s"]
    layers = sum(m[k] for k in READERS[1:5])
    assert 0.95 * replan_ms <= layers <= replan_ms
    assert m["unspanned_idle_pct.replan"] < 5.0

    cfg = json.loads((tree.parent / "bench/configs/tiny.json").read_text())
    tf = json.loads((tree.parent / "bench/traffic/tiny-replan.json")
                    .read_text())
    base = traffic.philly_jobs(cfg["jobs"])
    rounds = []
    for i in range(tf["pool"]):
        jobs = traffic.permuted(base, seed, i)
        req = ScheduleRequest(
            cluster=replan._cluster(cfg),
            jobs=[Job(**j._asdict()) for j in jobs],
            horizon=replan.horizon(cfg, len(jobs)), u=cfg["u"],
            params=dict(tf["params"]))
        before = api.SEARCH_COUNTS["rounds"]
        get_policy(tf["policy"])(req)
        rounds.append(api.SEARCH_COUNTS["rounds"] - before)
    assert m["search_rounds_per_replan"] == pytest.approx(
        sum(rounds) / len(rounds))


def record(log_dir: str) -> None:
    """Record ``data/replan_trace.xplane.pb`` under ``log_dir``: the tiny
    cell's configuration, one pool member, every batch dispatched, with
    the Python tracer and HLO protos off to keep the file small."""
    import jax

    from repro.core import Job, ScheduleRequest, get_policy
    from repro.kernels import placement

    from bench.drivers import replan
    from bench.lib import traffic

    placement.DISPATCH_MIN_ROWS = 0
    cfg = json.loads((ROOT / "bench/configs/philly-20srv.json").read_text())
    cfg.update(cluster=TINY_CLUSTER, horizon_min=200)
    cfg["jobs"]["mix"] = [[1, 8], [2, 4], [4, 4], [8, 2]]
    jobs = traffic.permuted(traffic.philly_jobs(cfg["jobs"]), 3, 0)
    req = ScheduleRequest(cluster=replan._cluster(cfg),
                          jobs=[Job(**j._asdict()) for j in jobs],
                          horizon=replan.horizon(cfg, len(jobs)), u=cfg["u"],
                          params={"placement": "columnar",
                                  "columnar_backend": "jit"})
    policy = get_policy("sjf-bco")
    policy(req)                                     # compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"), \
            jax.profiler.TraceAnnotation("bench.replan"):
        policy(req)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    record(sys.argv[1])

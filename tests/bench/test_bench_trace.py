"""The trace reduction, on a small trace recorded on the CPU backend and
committed beside this file (three steps of a jitted product inside
``bench.*`` spans), and on hand-made device timelines with collectives."""
from __future__ import annotations

import pytest

from conftest import ROOT

from bench.lib import trace as tr

TRACE = ROOT / "tests/bench/data/cpu_trace.xplane.pb"


def test_committed_cpu_trace_reduces_the_same_way():
    ev = tr.load_events(str(TRACE), cpu_ops=True)
    assert list(ev["devices"]) == ["/host:CPU"]
    assert len(ev["devices"]["/host:CPU"]) == 21
    assert [s[0] for s in ev["spans"]] == \
        ["bench.traced"] + ["bench.batch_at", "bench.step"] * 3
    r = tr.reduce_trace(ev, tr.span_window(ev, "bench.traced"))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.029537596, abs=1e-12)
    assert r["busy_s"] == pytest.approx(0.000391053, abs=1e-12)
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    assert r["device_ops"][0][0] == "dot_general.1"
    assert r["device_ops"][0][1] == pytest.approx(0.000268852, abs=1e-12)
    assert sum(v for _, v in r["device_ops"]) == pytest.approx(
        r["busy_s"], rel=1e-9)
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"bench.batch_at", "bench.step"}
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)


def test_without_device_operations_is_an_error():
    ev = tr.load_events(str(TRACE))            # TPU planes only: none here
    with pytest.raises(ValueError):
        tr.reduce_trace(ev, tr.span_window(ev, "bench.traced"))


def _events():
    """Two devices.  Device 0: compute 0-4, an async permute 3-8 (start at
    3, done ending at 8), compute 6-7, a sync all-reduce 9-10.  Device 1:
    compute 0-10, a permute 2-3 hidden under it."""
    d0 = [("fusion.1", 0.0, 4.0), ("collective-permute-start.1", 3.0, 3.1),
          ("fusion.2", 6.0, 7.0), ("collective-permute-done.1", 7.5, 8.0),
          ("all-reduce.3", 9.0, 10.0)]
    d1 = [("fusion.1", 0.0, 10.0), ("collective-permute.4", 2.0, 3.0)]
    spans = [("bench.traced", 0.0, 12.0), ("bench.step", 0.0, 10.0),
             ("bench.batch_at", 10.0, 12.0)]
    return {"devices": {"/device:TPU:0": d0, "/device:TPU:1": d1},
            "spans": spans}


def test_collectives_and_exposure_by_hand():
    r = tr.reduce_trace(_events(), (0.0, 12.0))
    # Device 0: collectives 3-8 and 9-10 (6 s); compute 0-4 and 6-7, so
    # exposed 4-6, 7-8, 9-10 (4 s).  Device 1: 1 s, none exposed.
    assert r["collective_s"] == pytest.approx((6.0 + 1.0) / 2)
    assert r["collective_exposed_s"] == pytest.approx((4.0 + 0.0) / 2)
    # Busy: device 0 0-4, 6-7, 7.5-8, 9-10 (+ the 0.1 s start) = 6.5 s;
    # device 1 10 s.
    assert r["busy_s"] == pytest.approx((6.5 + 10.0) / 2)
    # Device 0's gaps 4-6, 7-7.5, 8-9 fall in bench.step, 10-12 in
    # bench.batch_at.
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.step": 3.5, "bench.batch_at": 2.0})


def test_window_clips_operations():
    r = tr.reduce_trace(_events(), (3.5, 9.5))
    assert r["window_s"] == pytest.approx(6.0)
    assert r["busy_s"] == pytest.approx((0.5 + 1.0 + 0.5 + 0.5 + 6.0) / 2)


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6)]) == \
        [(0, 2 - 1), (2, 4), (6, 10)]
    assert tr.length(tr.clip([(0, 10)], 2, 5)) == 3

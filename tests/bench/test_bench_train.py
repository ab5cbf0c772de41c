"""The training cells' comparison at a size a test run holds: a sound run
is correct; its float8 control and each planted fault -- state left
unchanged, half the batch left out, the exchange between chips left
out -- are not."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from conftest import ROOT

from bench import harness
from bench.drivers import train


def _run(tree):
    return harness.run_cell("vlm.train", 2 ** 31 + 3, 0.2, False,
                            time.perf_counter(), bench_json=tree,
                            require_tpu=False)


@pytest.mark.parametrize("tie", [False, True], ids=["own_head", "tied"])
def test_sound_run_is_correct(tree, tie):
    """The program and the reference agree with the output head as the
    configuration has it (its own matrix) and tied to the embedding."""
    path = tree.parent / "bench" / "configs" / "vlm.json"
    cfg = json.loads(path.read_text())
    cfg["model"]["tie_embeddings"] = tie
    path.write_text(json.dumps(cfg))
    out = _run(tree)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def test_state_left_unchanged_is_not_correct(tree, monkeypatch):
    from repro.optim import adamw

    def frozen(cfg, grads, params, state):
        return params, state, {"grad_norm": jax.numpy.zeros(()),
                               "lr": jax.numpy.zeros(())}

    monkeypatch.setattr(adamw, "apply", frozen)
    out = _run(tree)
    assert not out["correct"]
    assert out["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(tree, monkeypatch):
    from repro.dist import steps
    real = steps._grads_and_loss

    def half(model, ocfg, params, batch):
        return real(model, ocfg, params, jax.tree.map(
            lambda x: x[: x.shape[0] // 2], batch))

    monkeypatch.setattr(steps, "_grads_and_loss", half)
    out = _run(tree)
    assert not out["correct"]
    assert out["checks"]["loss_gap"]["value"] > \
        out["checks"]["loss_gap"]["limit"]


def test_float8_control_is_rejected(tree):
    """The reference one precision below the configuration's, in the
    program's place, fails the limits that sound runs pass."""
    spec = json.loads(tree.read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == "vlm.train")
    run = harness.Run(spec, cell, 5, 0.0, tree.parent / "bench")
    run.devices = jax.devices()
    rows = run.traffic["per_chip_batch"]
    for seed in (5, 6, 7):
        ref = train.reference(run, seed, rows)
        ctl = train.reference(run, seed, rows, fp8=True)
        gaps = train.compare(train.as_program(ctl), ref)
        assert any(gaps[k] > run.limits[k] for k in gaps), gaps


EXCHANGE = textwrap.dedent("""
    import json, sys, time
    from pathlib import Path
    sys.path[:0] = [{root!r}, {src!r}, {here!r}]
    from conftest import make_tree
    from bench import harness
    import repro.dist.steps as steps
    if {drop}:
        steps.ring_all_reduce = lambda x, axis: x
    bj = make_tree(Path({tmp!r}), width=2)
    out = harness.run_cell("vlm.train", 9, 0.2, False, time.perf_counter(),
                           bench_json=bj, require_tpu=False)
    print(json.dumps(out))
""")


@pytest.mark.parametrize("drop", [False, True], ids=["ring", "no_exchange"])
def test_exchange_left_out_is_not_correct(tmp_path, drop):
    code = EXCHANGE.format(root=str(ROOT), src=str(ROOT / "src"),
                           here=str(ROOT / "tests/bench"), drop=drop,
                           tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not drop), out["checks"]


def test_worst_gap_by_leaf():
    from bench.lib.model_ref import worst_gap
    ref = np.array([1.0, 2.0, 4.0, 1e-6])
    got = np.array([1.0, 2.2, 4.0, 2e-6])
    gap, i = worst_gap(got, ref)
    assert i == 1 and gap == pytest.approx(0.1)
    # A near-zero leaf is measured against the median leaf's norm.
    gap, i = worst_gap(np.array([1.0, 2.0, 4.0, 0.5]), ref)
    assert i == 3 and gap == pytest.approx(0.5 / 1.5, rel=1e-5)
    gap, _ = worst_gap(got, ref, keep=np.array([True, False, True, True]))
    assert gap < 1e-5


def test_calibrate_control_and_half_batch_read_above_the_limits(tree, monkeypatch):
    """calibrate.py's readings: the program passes the limits, and the
    float8 control and half the batch each fail one."""
    from bench import calibrate
    spec = json.loads(tree.read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == "vlm.train")
    run = harness.Run(spec, cell, 9, 0.0, tree.parent / "bench")
    run.devices = jax.devices()
    lines = []
    monkeypatch.setattr(calibrate, "emit", lambda kind, seed, **x:
                        lines.append(dict(x, kind=kind)))
    calibrate.train(run, [9, 10], [9])
    assert [x["kind"] for x in lines] == ["program", "control", "half_batch",
                                         "program"]
    for x in lines:
        above = [k for k in run.limits if x[k] > run.limits[k]]
        assert bool(above) is (x["kind"] != "program"), x

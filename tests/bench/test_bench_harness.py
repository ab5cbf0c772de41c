"""The benchmark's harness: cells, traffic and metric readers are found by
name, and a run refuses to start without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import ROOT

from bench import harness


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "philly-20srv.replan-device", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_committed_cells_name_their_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in spec["workloads"]:
        cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])
        assert (ROOT / cfg["file"]).is_file()
        tf = json.loads((ROOT / "bench/traffic" /
                         f"{cell['traffic']}.json").read_text())
        assert (ROOT / "bench/drivers" / f"{tf['driver']}.py").is_file()
        assert (ROOT / "bench/limits" / f"{cell['name']}.json").is_file()
    for m in spec["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}


def test_a_cell_and_a_metric_added_by_files_alone(tree):
    """A new traffic mix, cell, limits file and per-layer metric reader,
    with entries in BENCHMARK.json only, run with no harness edit."""
    bench = tree.parent / "bench"
    tf = json.loads((bench / "traffic" / "tiny-replan.json").read_text())
    tf.update(pool=1, params={})
    (bench / "traffic" / "tiny-host.json").write_text(json.dumps(tf))
    (bench / "limits" / "tiny.host.json").write_text(
        json.dumps({"replans_differing": 0}))
    (bench / "metrics" / "replans_seen.py").write_text(
        "def read(r):\n    return float(r['replans']) if r.get('replans') "
        "else None\n")
    spec = json.loads(tree.read_text())
    spec["workloads"].append({"name": "tiny.host", "config": "tiny",
                              "traffic": "tiny-host", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("tiny.host")
    spec["per_layer"].append({
        "name": "replans_seen", "unit": "replans", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "replan_s",
        "workloads": ["tiny.host"]})
    tree.write_text(json.dumps(spec))
    out = harness.run_cell("tiny.host", 7, 0.2, False, time.perf_counter(),
                           bench_json=tree, require_tpu=False)
    assert out["correct"] and out["attempted"] >= 1
    assert set(out["metrics"]) == {"replan_s", "setup_s"}
    readings = {"replans": out["attempted"]}
    assert harness.read_metric("replans_seen", readings, bench) == \
        out["attempted"]


def test_metric_applies_where_it_moves(tree):
    spec = json.loads(tree.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    by_name = {m["name"]: m for m in spec["per_layer"] + spec["end_to_end"]}
    assert harness.applies(by_name["probes_per_replan"], cells["tiny.replan"],
                           spec)
    assert not harness.applies(by_name["probes_per_replan"],
                               cells["vlm.train"], spec)
    assert harness.applies(by_name["setup_s"], cells["vlm.train"], spec)
    unlisted = {"name": "x", "moves": "train_tokens_per_s"}
    assert harness.applies(unlisted, cells["vlm.train"], spec)
    assert not harness.applies(unlisted, cells["tiny.replan"], spec)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.replan", {"probes_per_replan", "device_calls_per_replan",
                     "recheck_pct", "device_idle_pct.replan"}),
    ("vlm.train", {"input_ms_per_step", "train_mfu",
                   "device_idle_pct.train"})])
def test_result_line_keys(tree, monkeypatch, cell, metrics):
    from bench.lib import peaks
    from repro.kernels import placement
    monkeypatch.setattr(placement, "DISPATCH_MIN_ROWS", 0)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    out = harness.run_cell(cell, 2 ** 31 + 5, 0.2, True,
                           time.perf_counter(), bench_json=tree,
                           require_tpu=False)
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device",
            "breakdown"} <= set(out)
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(out["device"])
    assert out["device"]["busy_s"] > 0
    assert set(out["metrics"]) == metrics
    assert all(m["value"] >= 0 for m in out["metrics"].values())
    assert out["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("name", ["philly-20srv.json", "internvl2-1b.json"])
def test_configs_state_their_cuts(name):
    cfg = json.loads((ROOT / "bench/configs" / name).read_text())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    # A configuration no cell uses yet states no cut; one in use states
    # the cuts its BENCHMARK.json entry lists.
    entry = next((c for c in spec["configs"] if c["name"] == cfg["name"]),
                 {"reduced": []})
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["assumed"]


CACHE = """
import sys
sys.path[:0] = [{root!r}]
import jax
from pathlib import Path
from bench import harness
harness.use_compile_cache(Path({tmp!r}), {chips})
print(jax.config.jax_enable_compilation_cache,
      jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("chips", [1, 4])
def test_compile_cache_is_kept_for_one_chip_only(tmp_path, chips):
    """One-chip cells cache at the checkout's fixed path; multi-chip cells
    cache nothing, as the program itself does for multi-device runs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", CACHE.format(root=str(ROOT), tmp=str(tmp_path),
                                            chips=chips)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    enabled, where = proc.stdout.split()
    if chips == 1:
        assert enabled == "True" and where == str(tmp_path / ".jax_cache")
    else:
        assert enabled == "False"


def test_input_time_reads_the_window_steps_only():
    """The traced steps' spans come after the window's and are not read."""
    readings = {"spans": {"batch_at": [9.0] * 3 + [0.002] * 4 + [9.0] * 5},
                "batch_at_window": (3, 7), "steps": 4}
    assert harness.read_metric("input_ms_per_step", readings) == \
        pytest.approx(2.0)

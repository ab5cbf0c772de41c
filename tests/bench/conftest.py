"""Fixtures for the benchmark's CPU tests: a checkout-shaped directory
whose ``BENCHMARK.json`` names small cells of the same drivers, metric
readers and references as the real one."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY_CLUSTER = {"capacities": [8, 4, 8, 4, 16, 8], "b_intra": 300.0,
                "b_inter": 1.25, "gpu_speed": 50.0, "xi1": 0.7,
                "xi2": 0.002, "alpha": 0.3}
TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 512, "n_patches": 8,
              "rope_theta": 10000.0, "norm_eps": 1e-6,
              "tie_embeddings": False, "param_dtype": "float32",
              "compute_dtype": "float32", "remat": False}
# The cell of a test tree that stands in for the real cells of each kind
# (the kind a traffic file names).
TINY_CELLS = {"replan": "tiny.replan", "train": "vlm.train"}


def make_tree(root: Path, width: int = 1) -> Path:
    """A checkout-shaped tree under ``root`` with the cells
    ``tiny.replan`` and ``vlm.train``; returns its BENCHMARK.json."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = root / "bench"
    kind = {w["name"]: json.loads((b / "traffic" / f"{w['traffic']}.json")
                                  .read_text())["driver"]
            for w in spec["workloads"]}
    sched = json.loads((b / "configs" / "philly-20srv.json").read_text())
    sched.update(name="tiny", cluster=TINY_CLUSTER, horizon_min=200)
    sched["jobs"]["mix"] = [[1, 8], [2, 4], [4, 4], [8, 2]]
    (b / "configs" / "tiny.json").write_text(json.dumps(sched))
    vlm = json.loads((b / "configs" / "internvl2-1b.json").read_text())
    vlm.update(name="vlm", model=TINY_MODEL)
    (b / "configs" / "vlm.json").write_text(json.dumps(vlm))
    tf = json.loads((b / "traffic" / "rar-w1.json").read_text())
    tf.update(width=width, per_chip_batch=2, seq=24, traced_steps=2,
              reference_block=1)
    (b / "traffic" / "tiny-train.json").write_text(json.dumps(tf))
    tf = json.loads((b / "traffic" / "replan-device.json").read_text())
    tf.update(pool=2)
    (b / "traffic" / "tiny-replan.json").write_text(json.dumps(tf))
    (b / "limits" / "tiny.replan.json").write_text(
        (b / "limits" / "philly-20srv.replan-device.json").read_text())
    (b / "limits" / "vlm.train.json").write_text(json.dumps(
        {"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "change_norm_gap": 1e-3}))
    spec["configs"] += [
        {"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
         "reduced": [], "why": "test"},
        {"name": "vlm", "source": "test", "file": "bench/configs/vlm.json",
         "reduced": [], "why": "test"}]
    spec["workloads"] = [
        {"name": "tiny.replan", "config": "tiny", "traffic": "tiny-replan",
         "chips": 1, "why": "test"},
        {"name": "vlm.train", "config": "vlm", "traffic": "tiny-train",
         "chips": width, "why": "test"}]
    # Every metric as BENCHMARK.json gives it, reported in the test cell
    # of the kind of each real cell that lists it.
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({TINY_CELLS[kind[w]]
                                     for w in m["workloads"]
                                     if kind[w] in TINY_CELLS})
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(spec, indent=1))
    return path


@pytest.fixture
def tree(tmp_path) -> Path:
    return make_tree(tmp_path)


@pytest.fixture(autouse=True)
def _keep_cwd():
    cwd = os.getcwd()
    yield
    os.chdir(cwd)

"""Readings from which each cell's limits are set, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 ... \\
        [--control-seeds 1 2 3]

Prints one JSON line per reading:

* ``program``: the numbers compared for a sound run of the program (the
  lower reading is the largest over the seeds);
* ``control``: the plain reference computed one precision below the one
  the configuration states, in the program's place (float32 re-plans for
  the float64 scheduler; float8 products for the bfloat16 training step);
* the training fault planted in the reference put in the program's
  place: ``half_batch`` (half of each batch left out, the mean over the
  rest).  A state left unchanged reads 1 on the change's norm by
  construction and needs no run.

The training cells build the step once and read every seed in one
process.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def emit(kind: str, seed: int, **numbers) -> None:
    print(json.dumps({"kind": kind, "seed": seed, **numbers}), flush=True)


def replan(run, seeds, control_seeds) -> None:
    import numpy as np

    from bench.drivers import replan as drv
    from bench.lib import sched_ref
    cfg = run.config
    for seed in sorted(set(seeds) | set(control_seeds)):
        run.seed = seed
        state = drv.setup(run)
        for kind, dtype in (("program", None), ("control", np.float32)):
            if kind == "control" and seed not in control_seeds:
                continue
            if kind == "program" and seed not in seeds:
                continue
            differing = 0
            for member, req in zip(state["pool"], state["requests"]):
                h = drv.horizon(cfg, len(member))
                ref = sched_ref.sjf_bco(cfg["cluster"], member, h, cfg["u"])
                got = state["policy"](req) if dtype is None else \
                    sched_ref.sjf_bco(cfg["cluster"], member, h, cfg["u"],
                                      dtype)
                differing += bool(sched_ref.schedule_diff(got, ref))
            emit(kind, seed, replans_differing=differing,
                 replans=len(state["pool"]))


def train(run, seeds, control_seeds) -> None:
    import numpy as np

    from bench.drivers import train as drv
    built = drv.build(run)
    tf = run.traffic
    rows = tf["width"] * tf["per_chip_batch"]
    for seed in seeds:
        t0 = time.perf_counter()
        state = drv.start(run, built, seed)
        prog = {k: state[k] for k in ("setup_losses", "grad_norms",
                                      "change_norms")}
        del state
        gc.collect()
        ref = drv.reference(run, seed, rows)
        raw = ref["grad_raw_norms"]
        emit("program", seed, seconds=time.perf_counter() - t0,
             losses=prog["setup_losses"], ref_losses=ref["losses"],
             leaves=len(raw), left_out=int((raw < 1e-3 * np.median(raw))
                                           .sum()),
             **drv.compare(prog, ref))
        if seed not in control_seeds:
            continue
        for kind, n, fp8 in (("control", rows, True),
                             ("half_batch", rows // 2, False)):
            other = drv.reference(run, seed, n, fp8=fp8)
            emit(kind, seed, **drv.compare(drv.as_program(other), ref))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    from bench import harness
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.COMPILE_CACHE)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    run = harness.Run(spec, cell, args.seeds[0], 0.0, ROOT / "bench")
    run.devices = harness.find_devices(run.chips, require_tpu=True)
    harness.use_compile_cache(ROOT, run.chips)
    {"replan": replan, "train": train}[run.traffic["driver"]](
        run, args.seeds, args.control_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())

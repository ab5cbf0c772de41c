"""Benchmark harness: one function per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = scheduler wall
time where applicable) plus the validation verdicts against the paper's
qualitative claims.  The roofline table (dry-run derived) is appended when
results/dryrun_single.json exists.
"""
from __future__ import annotations

import sys
import time

import numpy as np


def _fig4() -> list[str]:
    from benchmarks import fig4_makespan as f4
    rows = f4.run(verbose=False)
    out = []
    for name in ("SJF-BCO", "FF", "LS", "RAND"):
        sel = [r for r in rows if r["policy"] == name]
        us = np.mean([r["sched_time_s"] for r in sel]) * 1e6
        ms = np.mean([r["makespan"] for r in sel])
        jct = np.mean([r["avg_jct"] for r in sel])
        out.append(f"fig4_{name},{us:.0f},makespan={ms:.0f};avg_jct={jct:.0f}")
    v = f4.validate(rows)
    out.append(f"fig4_validation,0,{';'.join(f'{k}={v[k]}' for k in v)}")
    return out


def _fig5() -> list[str]:
    from benchmarks import fig5_kappa as f5
    t0 = time.time()
    rows = f5.run(verbose=False)
    us = (time.time() - t0) / len(rows) * 1e6
    v = f5.validate(rows)
    curve = ";".join(f"k{r['kappa']}={r['makespan']:.0f}" for r in rows)
    return [f"fig5_kappa_sweep,{us:.0f},{curve}",
            f"fig5_validation,0,{';'.join(f'{k}={v[k]}' for k in v)}"]


def _fig6() -> list[str]:
    from benchmarks import fig6_servers as f6
    t0 = time.time()
    rows = f6.run(verbose=False)
    us = (time.time() - t0) / len(rows) * 1e6
    v = f6.validate(rows)
    out = []
    for name in ("SJF-BCO", "FF", "LS"):
        curve = ";".join(f"s{r['servers']}={r['makespan']:.0f}"
                         for r in rows if r["policy"] == name)
        out.append(f"fig6_{name},{us:.0f},{curve}")
    out.append(f"fig6_validation,0,{';'.join(f'{k}={v[k]}' for k in v)}")
    return out


def _fig7() -> list[str]:
    from benchmarks import fig7_lambda as f7
    t0 = time.time()
    rows = f7.run(verbose=False)
    us = (time.time() - t0) / len(rows) * 1e6
    v = f7.validate(rows)
    curve = ";".join(f"l{r['lambda']:.0f}={r['makespan']:.0f}" for r in rows)
    return [f"fig7_lambda_sweep,{us:.0f},{curve}",
            f"fig7_validation,0,{';'.join(f'{k}={v[k]}' for k in v)}"]


def _rar() -> list[str]:
    from benchmarks import rar_microbench
    return [f"rar_{l}" for l in rar_microbench.run(verbose=False)]


def _ablations() -> list[str]:
    from benchmarks import ablations
    return ablations.run(verbose=False)


def _roofline() -> list[str]:
    from benchmarks import roofline_report
    rows = roofline_report.run(verbose=False)
    out = []
    for r in rows:
        out.append(
            f"roofline_{r['arch']}_{r['shape']},0,"
            f"t_comp={r['t_compute_s']:.2e};t_mem={r['t_memory_s']:.2e};"
            f"t_coll={r['t_collective_s']:.2e};bound={r['bottleneck']};"
            f"mem_gib={r['hbm_peak_bytes']/2**30:.1f}")
    if not out:
        out = ["roofline,0,NO_DRYRUN_JSON(run repro.launch.dryrun first)"]
    return out


def main() -> None:
    sections = [("fig4 makespan-vs-policy", _fig4),
                ("fig5 kappa sweep", _fig5),
                ("fig6 servers sweep", _fig6),
                ("fig7 lambda sweep", _fig7),
                ("rar microbench", _rar),
                ("ablations (beyond-paper)", _ablations),
                ("roofline (dry-run derived)", _roofline)]
    failures = 0
    print("name,us_per_call,derived")
    for title, fn in sections:
        print(f"# {title}", file=sys.stderr)
        try:
            for row in fn():
                print(row)
        except Exception as e:                              # noqa: BLE001
            failures += 1
            import traceback
            traceback.print_exc()
            print(f"{title.replace(' ', '_')},0,FAILED({type(e).__name__})")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

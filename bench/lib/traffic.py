"""Traffic generation, kept with the benchmark.

``philly_jobs`` draws the paper's §7 job mix (after the Philly trace's
job-width shares): for each (width, count) of the mix, ``count`` jobs with
iterations, gradient size, mini-batch and per-sample/backward times drawn
uniformly from the configured ranges.  The draw is fixed by the
configuration's ``draw_seed``; a run's ``--seed`` only permutes it, so
every seed re-plans the same multiset of jobs in another order (the job
id, and hence the order among jobs of one width, changes).

``vlm_batch`` is the training traffic: Zipf(a) text tokens folded into
the vocabulary and unit-Gaussian patch embeddings, from a generator keyed
by (seed, step, crc32 of the model name) -- the same rows the program's
data pipeline is asked to produce.
"""
from __future__ import annotations

import zlib
from typing import NamedTuple

import numpy as np


class JobSpec(NamedTuple):
    jid: int
    num_gpus: int
    iters: int
    grad_size: float
    batch: int
    dt_fwd: float
    dt_bwd: float
    lam: float


def philly_jobs(jobs_cfg: dict) -> list[JobSpec]:
    """The configured mix, drawn once from ``draw_seed`` (ids in draw
    order)."""
    rng = np.random.default_rng(jobs_cfg["draw_seed"])
    out: list[JobSpec] = []
    for gpus, count in jobs_cfg["mix"]:
        for _ in range(count):
            out.append(JobSpec(
                jid=len(out), num_gpus=int(gpus),
                iters=int(rng.integers(*jobs_cfg["iters_range"])),
                grad_size=float(rng.uniform(*jobs_cfg["grad_range"])),
                batch=int(rng.integers(*jobs_cfg["batch_range"])),
                dt_fwd=float(rng.uniform(*jobs_cfg["dt_fwd_range"])),
                dt_bwd=float(rng.uniform(*jobs_cfg["dt_bwd_range"])),
                lam=float(jobs_cfg["lam"])))
    return out


def permuted(jobs: list[JobSpec], seed: int, member: int) -> list[JobSpec]:
    """Pool member ``member`` of run seed ``seed``: the jobs in a seeded
    order, renumbered 0..n-1 in list order."""
    order = np.random.default_rng([seed, member]).permutation(len(jobs))
    return [jobs[i]._replace(jid=k) for k, i in enumerate(order)]


def vlm_batch(name: str, vocab: int, n_patches: int, d_model: int,
              batch: int, seq: int, seed: int, step: int,
              zipf_a: float) -> dict[str, np.ndarray]:
    """The global batch of ``step``: ``tokens`` [batch, seq - n_patches]
    int32 and ``patches`` [batch, n_patches, d_model] float32."""
    rng = np.random.default_rng((seed, step,
                                 zlib.crc32(name.encode()) & 0xFFFF))
    z = rng.zipf(zipf_a, size=(batch, seq - n_patches))
    tokens = ((z - 1) % vocab).astype(np.int32)
    patches = rng.standard_normal((batch, n_patches, d_model),
                                  dtype=np.float32)
    return {"tokens": tokens, "patches": patches}

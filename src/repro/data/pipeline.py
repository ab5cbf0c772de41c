"""Synthetic data pipeline: deterministic, shardable, family-aware.

Production shape: an infinite iterator of global batches keyed by step, so
every host can regenerate its shard without coordination (the same property
a deterministic tf.data/grain pipeline gives you).  Token streams follow a
Zipf distribution (more realistic softmax/router load than uniform);
modality stubs (patches/frames) are unit Gaussians.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Iterator

import numpy as np

from repro.models.config import InputShape, ModelConfig


@dataclasses.dataclass(frozen=True)
class DataConfig:
    seed: int = 0
    zipf_a: float = 1.3


def _tokens(rng: np.random.Generator, shape, vocab: int, a: float):
    z = rng.zipf(a, size=shape)
    return ((z - 1) % vocab).astype(np.int32)


def make_batch(cfg: ModelConfig, shape: InputShape, step: int,
               data_cfg: DataConfig = DataConfig(),
               batch_override: int | None = None) -> dict:
    """Deterministic global batch for (arch, shape, step)."""
    # crc32, not hash(): str hashes are salted per process.
    rng = np.random.default_rng((data_cfg.seed, step,
                                 zlib.crc32(cfg.name.encode()) & 0xFFFF))
    B = batch_override or shape.global_batch
    S = shape.seq_len
    if cfg.family == "vlm":
        return {
            "tokens": _tokens(rng, (B, S - cfg.n_patches), cfg.vocab,
                              data_cfg.zipf_a),
            "patches": rng.standard_normal(
                (B, cfg.n_patches, cfg.d_model), dtype=np.float32),
        }
    if cfg.family == "audio":
        return {
            "frames": rng.standard_normal(
                (B, cfg.enc_frames, cfg.d_model), dtype=np.float32),
            "tokens": _tokens(rng, (B, S), cfg.vocab, data_cfg.zipf_a),
        }
    return {"tokens": _tokens(rng, (B, S), cfg.vocab, data_cfg.zipf_a)}


def batch_iterator(cfg: ModelConfig, shape: InputShape,
                   data_cfg: DataConfig = DataConfig(),
                   batch_override: int | None = None) -> Iterator[dict]:
    step = 0
    while True:
        yield make_batch(cfg, shape, step, data_cfg, batch_override)
        step += 1

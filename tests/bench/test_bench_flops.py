"""The counted work of a training step and the least bytes of an
all-reduce, against hand counts at a reduced size, and the peak table."""
from __future__ import annotations

import json

import pytest

from conftest import ROOT

from bench.lib import flops, peaks

SMALL = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab": 50, "n_patches": 3}


def _hand_count(m, batch, seq):
    """Every product of the step written out as (rows, cols, inner) and
    counted 2 * rows * cols * inner; backward as 2x forward, except the
    projector whose input is data (1x)."""
    d, hd, ff = m["d_model"], m["head_dim"], m["d_ff"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    text = seq - m["n_patches"]
    layer = [(seq, q, d), (seq, kv, d), (seq, kv, d), (seq, d, q),
             (seq, ff, d), (seq, ff, d), (seq, d, ff)]
    attn = 0
    for i in range(seq):                       # causal: i + 1 keys
        attn += m["n_heads"] * (2 * (i + 1) * hd) * 2     # QK^T and PV
    fwd = sum(2 * a * b * c for a, b, c in layer) * m["n_layers"] \
        + attn * m["n_layers"] + 2 * text * m["vocab"] * d
    proj = 2 * m["n_patches"] * d * d
    return batch * (3 * fwd + 2 * proj)


@pytest.mark.parametrize("batch,seq", [(1, 5), (3, 9)])
def test_train_flops_by_hand(batch, seq):
    assert flops.vlm_train_flops(SMALL, batch, seq) == \
        _hand_count(SMALL, batch, seq)


def test_full_size_step():
    cfg = json.loads((ROOT / "bench/configs/internvl2-1b.json").read_text())
    f = flops.vlm_train_flops(cfg["model"], 2, 4096)
    assert f == pytest.approx(2.5687e13, rel=1e-3)


def test_peak_table():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    assert peaks.peak("TPU v5 lite", "ici_bytes_per_s") == 200e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary", "bf16_flops")

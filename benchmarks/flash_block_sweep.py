"""Block-size sweep of the training step's causal flash attention.

Times each of JAX's bundled TPU flash-attention kernels alone -- the
forward (with the residuals the backward needs), the dk/dv kernel and
the dq kernel -- at every (query block, key block) pair of ``BLOCKS``, at
the shape of the ``internvl2-1b.rar-w1`` cell (2 x 4,096 positions, 14
heads of 64, bf16), then the whole forward and backward through
``kernels.ops.causal_flash_attention`` at the pinned rule
(``ops.causal_blocks``) beside the jnp query-chunked path it replaces.
Needs a TPU; prints one line a case and writes them to
``chiprun_out/flash_block_sweep.json``.

    PYTHONPATH=src python benchmarks/flash_block_sweep.py
"""
from __future__ import annotations

import itertools
import json
import math
import os
import statistics
import time

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import flash_attention as jfa

from repro.kernels import ops
from repro.models.layers import _sdpa_q_chunked

B, H, K, S, HD = 2, 14, 2, 4096, 64
BLOCKS = (256, 512, 1024)
REPS = 10


def _time(fn, *args) -> float:
    """Median milliseconds of ``REPS`` calls after two warm-ups."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times)


def main() -> None:
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_block_sweep needs a TPU")
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    bf = jnp.bfloat16
    q, do = (jax.random.normal(k, (B, H, S, HD), bf) for k in ks[:2])
    k, v = (jax.random.normal(kk, (B, H, S, HD), bf) for kk in ks[2:])
    scale = 1.0 / math.sqrt(HD)
    rows = []

    def record(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    for bq, bk in itertools.product(BLOCKS, BLOCKS):
        fwd = jax.jit(lambda q, k, v, bq=bq, bk=bk: jfa._flash_attention_impl(
            q, k, v, None, None, True, True, scale, 1, bq, bk, bk, False))
        try:
            o, l, m = fwd(q, k, v)
            di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1)
            record(kernel="fwd", block_q=bq, block_k=bk,
                   ms=_time(fwd, q, k, v))
            dkv = jax.jit(lambda *a, bq=bq, bk=bk: jfa._flash_attention_bwd_dkv(
                *a, block_q_major=bq, block_q=bq, block_k_major=bk,
                block_k=bk, sm_scale=scale, causal=True))
            record(kernel="dkv", block_q=bq, block_k=bk,
                   ms=_time(dkv, q, k, v, None, None, l, m, do, di))
            dq = jax.jit(lambda *a, bq=bq, bk=bk: jfa._flash_attention_bwd_dq(
                *a, block_q_major=bq, block_k_major=bk, block_k=bk,
                sm_scale=scale, causal=True, mask_value=jfa.DEFAULT_MASK_VALUE,
                debug=False)[0])
            record(kernel="dq", block_q=bq, block_k=bk,
                   ms=_time(dq, q, k, v, None, None, l, m, do, di))
        except Exception as e:                          # noqa: BLE001
            record(kernel="failed", block_q=bq, block_k=bk,
                   error=str(e).splitlines()[0][:200])

    # Whole attention of one layer, model layout, forward and backward:
    # the jnp path it replaces, the kernel's own defaults, the pinned rule
    # and each kernel's fastest pair of the sweep (``fastest`` in the
    # JSON: the values of ``ops.FWD_BLOCKS``, ``DKV_BLOCKS``, ``DQ_BLOCKS``).
    best = {}
    for kern in ("fwd", "dkv", "dq"):
        r = min((r for r in rows if r["kernel"] == kern),
                key=lambda r: r["ms"])
        best[kern] = (r["block_q"], r["block_k"])
    qm, gm = q.transpose(0, 2, 1, 3), do.transpose(0, 2, 1, 3)
    km, vm = (t[:, :K].transpose(0, 2, 1, 3) for t in (k, v))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def chunked(q, k, v):
        return _sdpa_q_chunked(q, k, v, pos, pos, causal=True, window=0,
                               softcap=0.0, compute_dtype=bf, q_chunk=512)

    cases = {"jnp_chunked": chunked}
    for name, blocks in (("kernel_defaults", jfa.BlockSizes.get_default(
            B, H, S, S, HD)), ("pinned_rule", ops.causal_blocks(S)),
            ("sweep_fastest", ops.causal_blocks(S, *best.values()))):
        cases[name] = lambda q, k, v, b=blocks: ops.causal_flash_attention(
            q, k, v, blocks=b).reshape(B, S, H * HD)
    for name, f in cases.items():
        step = jax.jit(lambda q, k, v, g, f=f: jax.vjp(f, q, k, v)[1](g))
        record(kernel=f"{name}_fwd_bwd",
               ms=_time(step, qm, km, vm, gm.reshape(B, S, H * HD)))
    print(json.dumps({"fastest": best}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_block_sweep.json", "w") as fh:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows,
                   "fastest": best}, fh, indent=1)


if __name__ == "__main__":
    main()

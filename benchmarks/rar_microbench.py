"""RAR vs fused all-reduce micro-benchmark (§3 / §Perf ablation).

Runs in this process on every device present (a 1-D ``("data",)`` mesh),
so on a four-chip host it is a four-chip ring.  Reports wall time per
gradient exchange and the HLO collective schedule of each variant
(2(w-1) collective-permutes vs one fused all-reduce), each row tagged with
the platform, device kind and device count it ran on.

    PYTHONPATH=src python benchmarks/rar_microbench.py
"""
from __future__ import annotations

import time


def run(verbose: bool = True, shard_elems: int = 1 << 20) -> list[str]:
    """``name,us_per_call,derived`` rows for the ring and the fused psum."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.dist.rar import ring_all_reduce
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    w = len(devices)
    mesh = make_mesh((w,), ("data",))
    x = jax.device_put(jnp.ones((w, shard_elems), jnp.float32),
                       NamedSharding(mesh, P("data")))
    where = f"{devices[0].platform}:{devices[0].device_kind}x{w}"
    lines = []
    for tag, fn in (("rar_ring_2w-1_steps",
                     lambda v: ring_all_reduce(v, "data")),
                    ("xla_fused_allreduce",
                     lambda v: jax.lax.psum(v, "data"))):
        jitted = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                                       out_specs=P("data")))
        txt = jitted.lower(x).compile().as_text()
        permutes = txt.count("collective-permute(")
        allreduces = txt.count("all-reduce(")
        jitted(x).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            out = jitted(x)
        out.block_until_ready()
        us = (time.perf_counter() - t0) / 20 * 1e6
        lines.append(f"{tag},{us:.1f},permutes={permutes};"
                     f"allreduces={allreduces};devices={where}")
    if verbose:
        for line in lines:
            print("  " + line)
    return lines


if __name__ == "__main__":
    run()

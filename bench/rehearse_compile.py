"""Compile the training cells' step for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py [--per-chip 8 4 2] \
        [--widths 1 4]

For each ring width given (the one-chip cell's, and the ring of four a
four-chip cell would run) and each per-chip batch given, at the sequence
length of the ``rar-w1`` traffic file, compiles ``make_rar_train_step``
for the first ``width`` chips of a ``v5e:2x2`` topology description and
prints the compiled program's ``memory_analysis()``: the bytes one chip
holds (arguments + outputs - aliased + temporaries) against the chip's
16 GB.
This is the basis of the per-chip batch the traffic files state.  A
rehearsal script, not a test: it loads the TPU compiler and takes minutes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--per-chip", type=int, nargs="+", default=[8])
    ap.add_argument("--widths", type=int, nargs="+", default=[1, 4])
    args = ap.parse_args(argv)

    import dataclasses

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from bench.drivers.train import PROGRAM_KEYS
    from repro.configs import get_config, input_specs
    from repro.dist.steps import make_rar_train_step
    from repro.models import build_model
    from repro.models.config import InputShape
    from repro.optim import adamw
    from repro.optim.adamw import AdamWConfig

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = json.loads((ROOT / "bench/configs/internvl2-1b.json").read_text())
    m = cfg["model"]
    pcfg = dataclasses.replace(get_config(cfg["program_arch"]),
                               **{k: m[k] for k in PROGRAM_KEYS})
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    seq = json.loads((ROOT / "bench/traffic/rar-w1.json").read_text())["seq"]
    for w in args.widths:
        model = build_model(pcfg, max_seq=seq)
        ocfg = AdamWConfig()
        mesh = Mesh(np.asarray(topo.devices[:w]), ("data",))

        def on(tree, spec):
            s = NamedSharding(mesh, spec)
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                tree)

        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        opt = jax.eval_shape(partial(adamw.init, ocfg), params)
        for per_chip in args.per_chip:
            batch = input_specs(pcfg, InputShape("bench", seq, w * per_chip,
                                                 "train"))
            compiled = make_rar_train_step(model, ocfg, mesh).lower(
                on(params, P()), on(opt, P()), on(batch, P("data"))).compile()
            mem = compiled.memory_analysis()
            held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                    - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
            print(json.dumps({
                "width": w, "seq": seq, "per_chip_batch": per_chip,
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "held_bytes": held, "fits_16GB": held < 16e9}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

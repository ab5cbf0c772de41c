"""Reproduction package for "On Scheduling Ring-All-Reduce Learning Jobs
in Multi-Tenant GPU Clusters with Communication Contention".

Subpackages:

* ``repro.core``    -- contention model, policy registry, simulator, theory
* ``repro.dist``    -- RAR collectives, sharding rules, train/serve steps
* ``repro.models``  -- the 10 assigned architectures (6 families)
* ``repro.kernels`` -- Pallas TPU kernels (interpret mode on CPU)
* ``repro.launch``  -- dry-run / train / serve / scheduler-launch drivers

The JAX parts target the pinned ``jax==0.9.0`` (see ``pyproject.toml``).
"""

"""Reusable TPU parallelism primitives.

The serving/tooling layers of this framework mirror the reference client
(which is single-process — SURVEY.md §2.4); this package holds the
framework-side scaling machinery the reference outsources to its server:

- :mod:`.mesh` — named-axis device mesh construction (greedy factorization
  under per-axis divisibility limits).
- :mod:`.collectives` — hand-rolled shard_map collectives: causal ring
  attention over a sequence-parallel axis, replicated-gradient psum sync.
- :mod:`.multihost` — jax.distributed bootstrap for multi-host (DCN)
  deployments of the serving harness.

The flagship transformer (models/transformer.py) composes these into its
5-axis (dp, pp, ep, sp, tp) training/forward step.
"""

from .collectives import (replicated_axes, ring_attention,
                          sync_replicated_grads)
from .mesh import build_mesh, factorize_mesh
from .multihost import initialize_multihost

__all__ = [
    "build_mesh",
    "factorize_mesh",
    "initialize_multihost",
    "replicated_axes",
    "ring_attention",
    "sync_replicated_grads",
]

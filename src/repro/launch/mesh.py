"""Production mesh builders.

Importing this module never touches jax device state — meshes are built
inside functions only (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (data, model) single pod; 2x16x16 (pod, data, model) multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)

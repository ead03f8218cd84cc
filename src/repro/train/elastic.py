"""Elastic scaling: rebuild the mesh when the healthy device count changes.

Policy: the `model` axis is architecture-determined and fixed; elasticity
happens on the data axis (and the pod axis across pods).  A world-size
change therefore maps to `new_data = n_devices // model`, and a checkpoint
written at any data-size restores onto any other (checkpoints are stored
unsharded per-host, and resharding is just placing with new NamedShardings).

The data pipeline stays deterministic across re-meshes because the sampler
is a pure function of (seed, step) — hosts slice `batch_indices(...)` by
their new data-axis coordinate (see data/sampler.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from repro.dist.sharding import make_mesh


@dataclass
class ElasticDecision:
    ok: bool
    mesh_shape: Tuple[int, ...]
    axis_names: Tuple[str, ...]
    dropped_batch: int  # global batch rows dropped to stay divisible
    reason: str = ""


def plan_remesh(
    n_devices: int,
    model_parallel: int,
    global_batch: int,
    multi_pod: bool = False,
    pod_size: Optional[int] = None,
) -> ElasticDecision:
    """Compute the new mesh shape after a world-size change."""
    if n_devices % model_parallel != 0:
        return ElasticDecision(False, (), (), 0,
                               f"{n_devices} devices not divisible by "
                               f"model={model_parallel}")
    data = n_devices // model_parallel
    if multi_pod:
        assert pod_size, "pod_size required for multi-pod re-mesh"
        if n_devices % pod_size != 0:
            return ElasticDecision(False, (), (), 0,
                                   "device count not divisible by pod size")
        pods = n_devices // pod_size
        data = pod_size // model_parallel
        shape = (pods, data, model_parallel)
        names = ("pod", "data", "model")
        dp = pods * data
    else:
        shape = (data, model_parallel)
        names = ("data", "model")
        dp = data
    dropped = global_batch % dp
    return ElasticDecision(True, shape, names, dropped)


def build_mesh(decision: ElasticDecision) -> Mesh:
    assert decision.ok, decision.reason
    return make_mesh(decision.mesh_shape, decision.axis_names)


def reshard_state(state, new_shardings):
    """Place a (host-resident or differently-sharded) state pytree onto the
    new mesh. With jax.device_put the runtime moves/reslices as needed."""
    return jax.tree.map(jax.device_put, state, new_shardings)

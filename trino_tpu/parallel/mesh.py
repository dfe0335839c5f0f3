"""Query mesh: device topology + sharded page placement.

Reference parity: the scheduler's node topology (NodeScheduler/
InternalNodeManager) collapses, TPU-first, into a jax.sharding.Mesh with a
single 'workers' axis; split->node assignment (SURVEY §2.10 'source
parallelism') becomes host pages placed shard-by-shard onto the mesh.
Multi-host pods extend the same mesh across processes (single-controller
JAX); DCN boundaries stay outside this module.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from trino_tpu.page import Column, Page, device_notes, op_scope


class QueryMesh:
    """One query-engine worker per device along axis 'workers'."""

    AXIS = "workers"

    def __init__(self, devices: Optional[Sequence] = None):
        devices = list(devices if devices is not None else jax.devices())
        self.mesh = Mesh(np.array(devices), (self.AXIS,))
        self.n = len(devices)

    def device_of(self, shard: int):
        """The physical device executing worker `shard`'s task pipelines."""
        return self.mesh.devices.flat[shard]

    # ---------------------------------------------------------- placement

    def replicated(self, tree):
        spec = NamedSharding(self.mesh, P())
        return jax.device_put(tree, spec)

    def shard_pages(self, pages: List[Page]) -> Page:
        """Stack n per-worker pages into one global Page whose leading axis is
        sharded over the mesh (the split->node assignment step).

        Assembled via make_array_from_single_device_arrays so per-shard
        blocks that already live on their devices (e.g. the output of a
        previous exchange) are used in place — no host round trip and no
        cross-device stack."""
        assert len(pages) == self.n, f"need {self.n} pages, got {len(pages)}"
        sharding = NamedSharding(self.mesh, P(self.AXIS))
        devices = list(self.mesh.devices.flat)

        def stack(*leaves):
            blocks = [
                jax.device_put(jnp.expand_dims(jnp.asarray(leaf), 0), dev)
                for leaf, dev in zip(leaves, devices)]
            shape = (self.n,) + blocks[0].shape[1:]
            return jax.make_array_from_single_device_arrays(
                shape, sharding, blocks)

        return jax.tree_util.tree_map(stack, *pages)

    def shard_map(self, fn: Callable, *, in_specs=None, out_specs=None,
                  check_rep: bool = False, replicated: int = 0) -> Callable:
        """Wrap fn as a per-shard program over the mesh (one Trino 'task'
        per device; collectives inside fn are the exchange data plane).

        Inputs stacked by shard_pages arrive as (1, ...) blocks per shard;
        fn sees them squeezed to per-worker shapes and its outputs are
        re-expanded so the global result keeps the sharded leading axis.
        The first `replicated` arguments are the same on every shard (a
        program's hoisted literals) and reach fn as they are.
        """
        if in_specs is None:
            in_specs = P(self.AXIS) if not replicated else None
        out_specs = out_specs if out_specs is not None else P(self.AXIS)

        def wrapped(*args):
            with op_scope("exchange__shard_view"):
                squeezed = jax.tree_util.tree_map(
                    lambda x: jnp.squeeze(x, axis=0), args[replicated:])
            with device_notes():    # a shard's scalars stay in its trace
                out = fn(*args[:replicated], *squeezed)
            with op_scope("exchange__shard_view"):
                return jax.tree_util.tree_map(
                    lambda x: jnp.expand_dims(x, axis=0), out)

        if in_specs is not None:
            return shard_map(wrapped, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_rep)

        def program(*args):
            # one spec per argument: its count is the call's
            specs = (P(),) * replicated \
                + (P(self.AXIS),) * (len(args) - replicated)
            return shard_map(wrapped, mesh=self.mesh, in_specs=specs,
                             out_specs=out_specs, check_vma=check_rep)(*args)
        return program

    def unshard(self, tree):
        """Fetch a sharded tree to host as per-shard list (axis 0)."""
        gathered = jax.device_get(tree)
        return gathered

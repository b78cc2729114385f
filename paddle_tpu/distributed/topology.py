"""Hybrid-parallel topology over a jax device mesh.

Role parity: `CommunicateTopology` / `HybridCommunicateGroup`
(`python/paddle/distributed/fleet/base/topology.py:61,174,228`) — the object
that carves the device set into dp/pp/sharding/sep/mp axes and hands each
parallelism layer its group.

TPU-first: instead of per-axis NCCL communicators, the topology owns ONE
`jax.sharding.Mesh` whose named axes are the hybrid axes; "groups" are mesh
axes (SPMD collectives ride ICI via named-axis reductions inside jit), and
pipeline stages are contiguous submeshes. No ring-ids, no communicator init:
XLA derives the communication from shardings.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# canonical axis order: pp outermost (stages = submeshes), then dp (data /
# zero-sharding axis), sep (sequence/context parallel), mp (tensor parallel)
AXES = ("pp", "dp", "sep", "mp")


class HybridTopology:
    def __init__(self, dp=1, mp=1, pp=1, sep=1, sharding=1, devices=None):
        if devices is None:
            devices = jax.devices()
        n = len(devices)
        # sharding (ZeRO) reuses the dp axis: stage-k sharding shards
        # states over dp (weight-update sharding); a distinct degree is
        # folded into dp for mesh purposes.
        self.dp_degree = dp
        self.mp_degree = mp
        self.pp_degree = pp
        self.sep_degree = sep
        self.sharding_degree = sharding
        need = dp * mp * pp * sep * max(1, sharding) // max(1, sharding)
        need = dp * mp * pp * sep
        if need == 1 and n > 1:
            # default: everything data-parallel
            dp = self.dp_degree = n
            need = n
        if need > n:
            raise ValueError(
                f"hybrid degrees dp={dp} mp={mp} pp={pp} sep={sep} need "
                f"{need} devices, have {n}")
        devices = devices[:need]
        arr = np.array(devices).reshape(self.pp_degree, self.dp_degree,
                                        self.sep_degree, self.mp_degree)
        self._dev_array = arr
        # global mesh including pp (used when pp==1 or for fully-SPMD cases)
        self.mesh = Mesh(arr, AXES)
        # per-stage submeshes for the pipeline runner
        self.stage_meshes = [
            Mesh(arr[i], AXES[1:]) for i in range(self.pp_degree)
        ]

    # --- paddle-style queries -------------------------------------------------
    def get_num_of_ranks(self):
        return int(self._dev_array.size)

    def get_hybrid_group_names(self):
        return list(AXES)

    @property
    def spmd_mesh(self):
        """Mesh used inside a single jit program (no pp axis when pp>1)."""
        if self.pp_degree == 1:
            return Mesh(self._dev_array[0], AXES[1:])
        return self.mesh

    def stage_mesh(self, stage):
        return self.stage_meshes[stage]

    def data_sharding(self, batch_ndim=1, extra_seq_axis=None):
        """NamedSharding for a data batch: batch dim over dp, optionally the
        sequence dim over sep."""
        spec = ["dp"] + [None] * (batch_ndim - 1)
        if extra_seq_axis is not None and self.sep_degree > 1:
            spec[extra_seq_axis] = "sep"
        return NamedSharding(self.spmd_mesh, P(*spec))

    def replicated(self):
        return NamedSharding(self.spmd_mesh, P())

    def param_sharding(self, placements):
        """placements: tuple per-dim of axis-name or None."""
        return NamedSharding(self.spmd_mesh, P(*placements))


_topology = None
_mesh_override = None  # pipeline stages trace against their submesh


def set_topology(topo):
    global _topology
    _topology = topo


def get_topology():
    global _topology
    if _topology is None:
        _topology = HybridTopology()
    return _topology


def reset_topology():
    global _topology
    _topology = None


def current_spmd_mesh():
    if _mesh_override is not None:
        return _mesh_override
    return get_topology().spmd_mesh


def traced_spmd_mesh():
    """The mesh of the SPMD program being traced right now (set by
    `use_spmd_mesh`: the train step, a pipeline stage), else None.
    Kernel dispatch asks this to learn it must run per shard — a default
    topology is no evidence that the current trace spans its devices."""
    return _mesh_override


import contextlib


@contextlib.contextmanager
def use_spmd_mesh(mesh):
    global _mesh_override
    old = _mesh_override
    _mesh_override = mesh
    try:
        yield
    finally:
        _mesh_override = old

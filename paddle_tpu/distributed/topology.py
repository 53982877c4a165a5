"""Hybrid-parallel topology over a jax Mesh.

Reference analog: CommunicateTopology / HybridCommunicateGroup
(/root/reference/python/paddle/distributed/fleet/base/topology.py:65,178) —
the 5-D rank grid [dp, pp, sharding, sep, mp] and its sub-groups.

TPU-native: the grid IS a jax.sharding.Mesh. Axis order is chosen for the
hardware, not the reference's NCCL rings: **mp (tensor parallel) innermost**
so TP collectives ride the fastest ICI dimension, then sep, sharding, pp,
dp outermost (DCN-friendly) — exactly the scaling-book recipe. Every
reference sub-group (get_model_parallel_group etc.) maps to a mesh axis
name usable by shard_map collectives.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import jax
from jax.sharding import Mesh

from . import collective, env

__all__ = ["CommunicateTopology", "HybridCommunicateGroup", "build_mesh",
           "build_hybrid_mesh", "get_hybrid_communicate_group",
           "set_hybrid_communicate_group", "get_mesh"]

_AXES = ["dp", "pp", "sharding", "sep", "mp"]  # outermost -> innermost

_current_hcg: Optional["HybridCommunicateGroup"] = None
_current_mesh: Optional[Mesh] = None


def build_mesh(dp=1, pp=1, sharding=1, sep=1, mp=1, devices=None) -> Mesh:
    """Build the hybrid mesh; mp innermost (fastest ICI)."""
    devices = devices if devices is not None else jax.devices()
    shape = (dp, pp, sharding, sep, mp)
    total = int(np.prod(shape))
    if total > len(devices):
        raise ValueError(
            f"topology {shape} needs {total} devices, have {len(devices)}")
    dev_array = np.asarray(devices[:total]).reshape(shape)
    return Mesh(dev_array, _AXES)


def _hybrid_device_array(ici, dcn, devices=None):
    """Device array for a two-tier mesh: per axis the extent is
    ``ici[i] * dcn[i]`` with the dcn factor outermost. Devices that carry
    a ``slice_index`` (multi-slice TPU) go through jax's own
    ``create_hybrid_device_mesh``; hosts whose devices have none (the CPU
    simulation) get a plain row-major reshape — the axis ORDER is
    preserved, which is all the static analyses consume."""
    devices = jax.devices() if devices is None else devices
    if hasattr(devices[0], "slice_index"):
        from jax.experimental import mesh_utils

        return mesh_utils.create_hybrid_device_mesh(
            tuple(ici), tuple(dcn), devices=devices)
    shape = tuple(int(d) * int(i) for i, d in zip(ici, dcn))
    total = int(np.prod(shape))
    if total > len(devices):
        raise ValueError(
            f"hybrid topology {shape} needs {total} devices, "
            f"have {len(devices)}")
    return np.asarray(devices[:total]).reshape(shape)


def build_hybrid_mesh(dp=1, pp=1, sharding=1, sep=1, mp=1,
                      dcn_dp=1, dcn_pp=1, dcn_sharding=1,
                      devices=None) -> Mesh:
    """Two-tier DCN×ICI hybrid mesh (multi-slice / multi-pod).

    Per axis the total extent is ``ici * dcn`` with the DCN factor
    outermost (slowest varying), so collectives along a pure-ICI axis
    never cross the data-center network.  Only the outer axes admit a
    DCN factor — mp/sep collectives are latency-bound and stay on ICI
    (the scaling-book rule the default ``build_mesh`` ordering encodes).

    The returned Mesh carries ``_pt_dcn_axes`` — the axis names with a
    DCN factor — which ``analysis.sharding.MeshSpec.from_mesh`` reads to
    tier the PT9xx reshard cost estimates (PT901 messages name the tier
    so a spec typo on a two-tier mesh is diagnosable from the text).
    """
    ici = (dp, pp, sharding, sep, mp)
    dcn = (dcn_dp, dcn_pp, dcn_sharding, 1, 1)
    dev_array = _hybrid_device_array(ici, dcn, devices)
    mesh = Mesh(dev_array, _AXES)
    dcn_axes = tuple(n for n, d in zip(_AXES, dcn) if int(d) > 1)
    try:
        object.__setattr__(mesh, "_pt_dcn_axes", dcn_axes)
    except Exception:  # ptlint: disable=PT502 — the annotation is a
        pass           # best-effort hint for MeshSpec.from_mesh; a
        #                frozen Mesh still works, just untied (ici)
    return mesh


def get_mesh() -> Optional[Mesh]:
    return _current_mesh


def set_mesh(mesh: Mesh):
    global _current_mesh
    _current_mesh = mesh


class CommunicateTopology:
    """reference: topology.py:65 — pure rank-grid arithmetic."""

    def __init__(self, hybrid_group_names=None, dims=None):
        self._parallel_names = hybrid_group_names or _AXES
        self._dims = list(dims or [1] * len(self._parallel_names))
        self.coordinate = None
        self._world = int(np.prod(self._dims))
        shape = tuple(self._dims)
        self._rank_grid = np.arange(self._world).reshape(shape)

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self):
        return self._world

    def get_rank(self, **kwargs):
        coord = tuple(kwargs[n] for n in self._parallel_names)
        return int(self._rank_grid[coord])

    def get_coord(self, rank):
        coord = np.unravel_index(rank, self._rank_grid.shape)
        import collections

        Coord = collections.namedtuple("Coord", self._parallel_names)
        return Coord(*[int(c) for c in coord])

    def get_axis_list(self, axis_name, index):
        ax = self._parallel_names.index(axis_name)
        sl = [slice(None)] * len(self._dims)
        sl[ax] = index
        return sorted(self._rank_grid[tuple(sl)].reshape(-1).tolist())

    def get_comm_list(self, axis_name):
        """All groups along `axis_name`: list of rank-lists."""
        ax = self._parallel_names.index(axis_name)
        moved = np.moveaxis(self._rank_grid, ax, -1)
        return moved.reshape(-1, self._dims[ax]).tolist()

    def get_rank_from_stage(self, global_rank, **kwargs):
        coord = self.get_coord(global_rank)._asdict()
        coord.update(kwargs)
        return self.get_rank(**coord)


class HybridCommunicateGroup:
    """reference: topology.py:178. Groups are mesh-axis-bound (collective.py
    Groups), so the same object drives eager API parity AND shard_map
    tracing."""

    def __init__(self, topology: CommunicateTopology):
        self._topo = topology
        self.global_rank = env.global_rank()
        self._dp_degree = topology.get_dim("dp")
        self._pp_degree = topology.get_dim("pp")
        self._sharding_degree = topology.get_dim("sharding")
        self._sep_degree = topology.get_dim("sep")
        self._mp_degree = topology.get_dim("mp")
        self.nranks = topology.world_size()

        coord = topology.get_coord(min(self.global_rank, self.nranks - 1))
        self._dp_rank = coord.dp
        self._pp_rank = coord.pp
        self._sharding_rank = coord.sharding
        self._sep_rank = coord.sep
        self._mp_rank = coord.mp

        def make_group(axis):
            comm_lists = self._topo.get_comm_list(axis)
            my_ranks = None
            for ranks in comm_lists:
                if self.global_rank in ranks:
                    my_ranks = ranks
                    break
            g = collective.new_group(my_ranks or comm_lists[0],
                                     axis_name=axis)
            return g

        self._dp_group = make_group("dp")
        self._pp_group = make_group("pp")
        self._sharding_group = make_group("sharding")
        self._sep_group = make_group("sep")
        self._mp_group = make_group("mp")
        # dp+sep fused group (reference get_dp_sep_parallel_group)
        self._dp_sep_group = self._dp_group
        self._pp_mp_group = self._mp_group

    # parallel mode dispatch (reference fleet/model.py:32)
    def get_parallel_mode(self):
        if self._pp_degree > 1:
            return "pipeline"
        if self._sharding_degree > 1 and self._dp_degree <= 1 and \
                self._mp_degree <= 1:
            return "sharding_parallel"
        if self._mp_degree > 1:
            return "tensor_parallel"
        if self._sep_degree > 1:
            return "segment_parallel"
        if self._dp_degree > 1:
            return "data_parallel"
        return "single"

    def topology(self):
        return self._topo

    def get_global_rank(self):
        return self.global_rank

    # -- data parallel
    def get_data_parallel_rank(self):
        return self._dp_rank

    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_data_parallel_group(self):
        return self._dp_group

    def get_data_parallel_group_src_rank(self):
        return self._dp_group.ranks[0]

    # -- model (tensor) parallel
    def get_model_parallel_rank(self):
        return self._mp_rank

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_model_parallel_group(self):
        return self._mp_group

    def get_model_parallel_group_src_rank(self):
        return self._mp_group.ranks[0]

    # -- pipeline
    def get_stage_id(self):
        return self._pp_rank

    def get_pipe_parallel_rank(self):
        return self._pp_rank

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_pipe_parallel_group(self):
        return self._pp_group

    def is_first_stage(self):
        return self._pp_rank == 0

    def is_last_stage(self):
        return self._pp_rank == self._pp_degree - 1

    def get_p2p_groups(self):
        return None

    # -- sharding
    def get_sharding_parallel_rank(self):
        return self._sharding_rank

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sharding_parallel_group(self):
        return self._sharding_group

    def get_sharding_parallel_group_src_rank(self):
        return self._sharding_group.ranks[0]

    # -- sep (context parallel)
    def get_sep_parallel_rank(self):
        return self._sep_rank

    def get_sep_parallel_world_size(self):
        return self._sep_degree

    def get_sep_parallel_group(self):
        return self._sep_group

    # -- fused axes
    def get_dp_sep_parallel_group(self):
        return self._dp_sep_group

    def get_pp_mp_parallel_group(self):
        return self._pp_mp_group

    def get_check_parallel_group(self, *a):
        return self._mp_group

    def get_rank_from_stage(self, stage_id, **kwargs):
        return self._topo.get_rank_from_stage(
            self.global_rank, pp=stage_id, **kwargs)

    def build_mesh(self) -> Mesh:
        mesh = build_mesh(self._dp_degree, self._pp_degree,
                          self._sharding_degree, self._sep_degree,
                          self._mp_degree)
        set_mesh(mesh)
        return mesh


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _current_hcg


def set_hybrid_communicate_group(hcg: HybridCommunicateGroup):
    global _current_hcg
    _current_hcg = hcg

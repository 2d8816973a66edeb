"""Device-grid topology.

Two layers:

* :class:`ProcessTopology` -- pure cartesian coordinate algebra over named
  axes (equivalent of reference ``runtime/pipe/topology.py:12``); used by the
  pipeline partitioner, checkpoint naming, and tests.  No devices needed.
* :class:`MeshTopology` -- binds a ``jax.sharding.Mesh`` with the canonical
  axis names ``('pp', 'dp', 'ep', 'sp', 'tp')``.  This replaces the
  reference's process-group machinery (``deepspeed/utils/groups.py``,
  ``runtime/pipe/topology.py:251`` PipelineParallelGrid): a "process group"
  becomes a mesh-axis subset, and collectives become XLA ops over those axes.

Axis layout rationale (TPU): the innermost mesh axis maps to the
fastest-wraparound ICI dimension, so we order axes outermost-to-innermost as
pp (lowest volume, p2p only), dp (ring allreduce), ep/sp (all-to-all), tp
(highest volume, per-layer collectives) -- mirroring the megascale convention
of keeping tensor-parallel traffic on the shortest links.
"""

from collections import namedtuple
from itertools import product as cartesian

import numpy as np

# Canonical mesh axis names.
PP_AXIS = "pp"
DP_AXIS = "dp"
ZSHARD_AXIS = "zshard"  # MiCS/hpZ secondary-partition subgroup (inner dp)
EP_AXIS = "ep"
SP_AXIS = "sp"
TP_AXIS = "tp"
ALL_AXES = (PP_AXIS, DP_AXIS, ZSHARD_AXIS, EP_AXIS, SP_AXIS, TP_AXIS)
# axes the batch dim is sharded over (ep is extra data parallelism outside
# MoE blocks; sp shards the sequence dim instead)
BATCH_AXES = (DP_AXIS, ZSHARD_AXIS, EP_AXIS)


class ProcessTopology:
    """Cartesian product of named axes; maps ranks <-> coordinates.

    The rank of a coordinate is its index in row-major (C) order over
    ``dims``, with ``axes[0]`` the outermost axis.
    """

    def __init__(self, axes, dims):
        self.axes = list(axes)
        self.dims = list(dims)
        assert len(self.axes) == len(self.dims)
        self.ProcessCoord = namedtuple("ProcessCoord", self.axes)
        self.mapping = {}
        for coord in cartesian(*[range(d) for d in self.dims]):
            key = self.ProcessCoord(**{axis: coord[self.axes.index(axis)] for axis in self.axes})
            self.mapping[key] = len(self.mapping)

    def get_rank(self, **coord_kwargs):
        if len(coord_kwargs) != len(self.axes):
            raise ValueError(f"get_rank() needs all axes {self.axes}, got {coord_kwargs}")
        key = self.ProcessCoord(**coord_kwargs)
        return self.mapping[key]

    def get_axis_names(self):
        return self.axes

    def get_rank_repr(self, rank, omit_axes=("data", "pipe"), inner_sep="_", outer_sep="-"):
        omit_axes = list(omit_axes)
        axes = [a for a in self.get_axis_names() if a not in omit_axes]
        names = []
        for ax in axes:
            ax_rank = getattr(self.get_coord(rank=rank), ax)
            names.append(f"{ax}{inner_sep}{ax_rank:02d}")
        return outer_sep.join(names)

    def get_dim(self, axis):
        if axis not in self.axes:
            return 0
        return self.dims[self.axes.index(axis)]

    def get_coord(self, rank):
        for coord, idx in self.mapping.items():
            if idx == rank:
                return coord
        raise ValueError(f"rank {rank} not found in topology")

    def get_axis_comm_lists(self, axis):
        """All rank-lists that vary only along ``axis`` (the axis "groups")."""
        if axis not in self.axes:
            return []
        other_axes = [a for a in self.axes if a != axis]
        lists = []
        for coord in cartesian(*[range(self.get_dim(a)) for a in other_axes]):
            other = dict(zip(other_axes, coord))
            ranks = [self.get_rank(**{axis: i}, **other) for i in range(self.get_dim(axis))]
            lists.append(ranks)
        return lists

    def filter_match(self, **filter_kwargs):
        """Ranks whose coordinates match all given axis=value filters."""

        def _match(coord):
            return all(getattr(coord, k) == v for k, v in filter_kwargs.items())

        return sorted(idx for coord, idx in self.mapping.items() if _match(coord))

    def get_axis_list(self, axis, idx):
        return [r for coord, r in self.mapping.items() if getattr(coord, axis) == idx]

    def world_size(self):
        return len(self.mapping)

    def __str__(self):
        return str(self.mapping)


class PipeDataParallelTopology(ProcessTopology):
    """2-axis pipe x data topology (reference ``topology.py:232``)."""

    def __init__(self, num_pp, num_dp):
        super().__init__(axes=["pipe", "data"], dims=[num_pp, num_dp])


class PipeModelDataParallelTopology(ProcessTopology):
    """3-axis pipe x data x model topology (reference ``topology.py:244``)."""

    def __init__(self, num_pp, num_mp, num_dp):
        super().__init__(axes=["pipe", "data", "model"], dims=[num_pp, num_dp, num_mp])


# --------------------------------------------------------------------------
# Mesh layer
# --------------------------------------------------------------------------

_GLOBAL_MESH = None


class MeshTopology:
    """A named `jax.sharding.Mesh` over (pp, dp, ep, sp, tp).

    ``dp`` here is the *pure* data-parallel degree after carving out expert
    parallelism: total data-parallel replicas = dp * ep (the ep axis is used
    as extra data parallelism outside MoE blocks, matching the reference's
    expert-data-parallel group algebra in ``utils/groups.py:113``).
    """

    def __init__(self, pp=1, dp=None, zshard=1, ep=1, sp=1, tp=1, devices=None):
        import jax
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        n = len(devices)
        if dp is None:
            denom = pp * zshard * ep * sp * tp
            assert n % denom == 0, (
                f"{n} devices not divisible by pp*zshard*ep*sp*tp={denom}")
            dp = n // denom
        assert pp * dp * zshard * ep * sp * tp == n, (
            f"mesh {pp}x{dp}x{zshard}x{ep}x{sp}x{tp} != {n} devices"
        )
        dev_array = np.asarray(devices).reshape(pp, dp, zshard, ep, sp, tp)
        self.mesh = Mesh(dev_array, ALL_AXES)
        self.sizes = dict(zip(ALL_AXES, (pp, dp, zshard, ep, sp, tp)))

    # -- axis sizes
    @property
    def pp(self):
        return self.sizes[PP_AXIS]

    @property
    def dp(self):
        return self.sizes[DP_AXIS]

    @property
    def zshard(self):
        return self.sizes[ZSHARD_AXIS]

    @property
    def ep(self):
        return self.sizes[EP_AXIS]

    @property
    def sp(self):
        return self.sizes[SP_AXIS]

    @property
    def tp(self):
        return self.sizes[TP_AXIS]

    @property
    def data_parallel_size(self):
        """Replication degree seen by the optimizer = dp * zshard * ep * sp.

        ZeRO shards over this combined group, matching the reference's
        seq-data-parallel group (``utils/groups.py:491``) and
        expert-data-parallel algebra.  ``zshard`` (MiCS/hpZ subgroups,
        reference ``runtime/zero/mics.py``, ``utils/groups.py:505``) is part
        of the data-parallel degree: MiCS shards state *within* a zshard
        group and replicates across dp.
        """
        return self.dp * self.zshard * self.ep * self.sp

    def axis_names(self):
        return ALL_AXES

    def __enter__(self):
        return self.mesh.__enter__()

    def __exit__(self, *a):
        return self.mesh.__exit__(*a)

    def process_topology(self):
        return ProcessTopology(list(ALL_AXES), [self.sizes[a] for a in ALL_AXES])


def constrain(x, spec):
    """Sharding-constrain ``x`` against the process-global mesh.

    The one shared implementation behind every module's layout hints:
    no-op when no mesh is installed (bare use); inside a partially-manual
    ``shard_map`` the constraint is re-expressed on the context's abstract
    mesh with Manual axes stripped from the spec (those dims are already
    local there).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = _GLOBAL_MESH
    if mesh is None:
        return x
    try:
        am = jax.sharding.get_abstract_mesh()
        manual = set()
        use_mesh = mesh.mesh
        if am is not None and not am.empty:
            use_mesh = am
            try:
                manual = {n for n, t in zip(am.axis_names, am.axis_types)
                          if "Manual" in str(t)}
            except Exception:
                manual = set()

        def strip(entry):
            if entry is None:
                return None
            if isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a not in manual)
                return kept if kept else None
            return None if entry in manual else entry

        spec2 = PartitionSpec(*[strip(e) for e in spec])
        return jax.lax.with_sharding_constraint(x, NamedSharding(use_mesh, spec2))
    except Exception:
        return x


def set_mesh(mesh_topology):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh_topology
    return mesh_topology


def get_mesh():
    """The process-global MeshTopology (auto-creates a pure-DP mesh)."""
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        _GLOBAL_MESH = MeshTopology()
    return _GLOBAL_MESH


def axis_size(axis):
    return get_mesh().sizes[axis]

"""Process/device topology math and the global device mesh.

Reimplements the pure-math core of the reference's
``deepspeed/runtime/pipe/topology.py`` (``ProcessTopology`` :12,
``PipeModelDataParallelTopology`` :244) and replaces its process-group
plumbing with a single ``jax.sharding.Mesh`` carrying named axes
``(pipe, data, expert, sequence, model)``.

Axis order is chosen for ICI locality: ``model`` (tensor parallel) is the
innermost/fastest-varying axis so TP collectives ride neighboring chips;
``pipe`` is outermost so stage boundaries can span DCN.
"""

from collections import namedtuple
from itertools import product as cartesian_product

import numpy as np

import jax
from jax.sharding import Mesh

from deepspeed_tpu.utils.logging import logger

# Canonical axis order, outermost -> innermost.
MESH_AXES = ("pipe", "data", "expert", "sequence", "model")


class ProcessTopology:
    """Cartesian product of parallelism axes -> rank mapping (pure math).

    Mirrors reference ``runtime/pipe/topology.py:12`` behavior: axes is a list
    of axis names ordered outermost-first, dims the matching sizes. The rank
    of a coordinate is its row-major index.
    """

    def __init__(self, axes, dims):
        assert len(axes) == len(dims)
        self.axes = list(axes)
        self.dims = list(dims)
        self.ProcessCoord = namedtuple("ProcessCoord", axes)
        self.mapping = {}
        ranges = [range(d) for d in dims]
        for global_rank, coord in enumerate(cartesian_product(*ranges)):
            key = dict(zip(axes, coord))
            self.mapping[self.ProcessCoord(**key)] = global_rank

    def get_rank(self, **coord_kwargs):
        if len(coord_kwargs) != len(self.axes):
            raise ValueError(f"get_rank() needs all axes {self.axes}")
        return self.mapping[self.ProcessCoord(**coord_kwargs)]

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
        """Lists of ranks that vary along `axis` with all others fixed."""
        if axis not in self.axes:
            return []
        other_axes = [a for a in self.axes if a != axis]
        lists = []
        ranges = [range(self.get_dim(a)) for a in other_axes]
        for coord in cartesian_product(*ranges):
            other = dict(zip(other_axes, coord))
            sub = [self.get_rank(**{axis: i}, **other) for i in range(self.get_dim(axis))]
            lists.append(sub)
        return lists

    def filter_match(self, **filter_kwargs):
        def _filter_helper(x):
            for key, val in filter_kwargs.items():
                if getattr(x, key) != val:
                    return False
            return True

        coords = filter(_filter_helper, self.mapping.keys())
        return [self.mapping[coord] for coord in coords]

    def get_axis_list(self, axis, idx):
        return self.filter_match(**{axis: idx})

    def world_size(self):
        return int(np.prod(self.dims)) if self.dims else 1

    def __str__(self):
        return str(self.mapping)


class PipeModelDataParallelTopology(ProcessTopology):
    """3D (pipe, data, model) topology (reference :244)."""

    def __init__(self, num_pp, num_dp, num_mp):
        super().__init__(axes=["pipe", "data", "model"], dims=[num_pp, num_dp, num_mp])


class PipeDataParallelTopology(ProcessTopology):
    def __init__(self, num_pp, num_dp):
        super().__init__(axes=["pipe", "data"], dims=[num_pp, num_dp])


def resolve_mesh_dims(mesh_config, n_devices, allow_subset=False):
    """Resolve -1 on at most one axis to 'all remaining devices'.

    `allow_subset=True` (inference) permits a mesh smaller than the host's
    device count; training keeps the strict all-devices check so a
    mis-sized config fails loudly instead of silently idling chips."""
    sizes = {ax: getattr(mesh_config, ax, 1) or 1 for ax in MESH_AXES}
    wild = [ax for ax, s in sizes.items() if s == -1]
    if len(wild) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {wild}")
    fixed = int(np.prod([s for s in sizes.values() if s != -1]))
    if wild:
        if n_devices % fixed != 0:
            raise ValueError(
                f"device count {n_devices} not divisible by fixed axes product {fixed}")
        sizes[wild[0]] = n_devices // fixed
    total = int(np.prod(list(sizes.values())))
    if total > n_devices or (total != n_devices and not allow_subset):
        raise ValueError(
            f"mesh {sizes} needs {total} devices but {n_devices} are available")
    return sizes


def make_mesh(mesh_config=None, devices=None, allow_subset=False):
    """Build the global Mesh from a MeshConfig (or use all devices on `data`)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if mesh_config is None:
        sizes = {ax: 1 for ax in MESH_AXES}
        sizes["data"] = n
    else:
        sizes = resolve_mesh_dims(mesh_config, n, allow_subset=allow_subset)
    shape = tuple(sizes[ax] for ax in MESH_AXES)
    total = int(np.prod(shape))
    devices = list(devices)[:total]
    from jax.experimental import mesh_utils
    try:
        device_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except Exception as e:
        # e.g. a device subset that is not a whole slice: the mesh still
        # builds, but say so — on a multi-chip host a flat reshape may
        # put mesh neighbours on chips that are not ICI neighbours
        logger.warning(
            f"create_device_mesh failed for mesh shape {shape} over "
            f"{len(devices)} device(s) ({type(e).__name__}: {e}); falling "
            "back to a flat reshape of the device list")
        device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, MESH_AXES)


def make_hybrid_mesh(mesh_config=None, dcn_sizes=None, devices=None,
                     allow_subset=False):
    """Multi-slice ICI x DCN mesh: per-axis size = ici * dcn (the
    t5x/MaxText hybrid split).  ``mesh_config`` carries the ICI
    (within-slice) sizes — ``-1`` resolves against the PER-SLICE device
    count — and ``dcn_sizes`` maps axis names to their across-slice
    (DCN) factors.  On real multi-slice TPU pods the device array comes
    from ``mesh_utils.create_hybrid_device_mesh`` (devices grouped by
    ``slice_index``, DCN-major per axis so ICI neighbors stay
    physically adjacent); single-slice/CPU runtimes — where devices
    carry no slice attribution — fall back to the same DCN-major
    per-axis layout over the flat device list, so the topology is pure
    config everywhere and CI exercises the exact axis arithmetic a pod
    run uses.

    Keep ``model`` (tensor parallel) ICI-only: a ``dcn_sizes['model']``
    factor is legal config but puts per-layer collectives on the slow
    across-slice links — the serving rule table maps ``slots`` to the
    DCN-spanning ``data`` axis precisely so per-token traffic never
    crosses DCN."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    dcn = {ax: int((dcn_sizes or {}).get(ax, 1) or 1) for ax in MESH_AXES}
    bad = [f"{ax}={s}" for ax, s in dcn.items() if s < 1]
    if bad:
        raise ValueError(f"dcn mesh sizes must be >= 1 (no -1 wildcard "
                         f"across slices): {', '.join(bad)}")
    unknown = set(dcn_sizes or {}) - set(MESH_AXES)
    if unknown:
        raise ValueError(f"unknown dcn mesh axes {sorted(unknown)}; "
                         f"valid axes: {MESH_AXES}")
    total_dcn = int(np.prod(list(dcn.values())))
    if n % total_dcn != 0:
        raise ValueError(
            f"dcn mesh {dcn_sizes} needs a device count divisible by "
            f"{total_dcn}, got {n}")
    ici = resolve_mesh_dims(mesh_config, n // total_dcn,
                            allow_subset=allow_subset) \
        if mesh_config is not None else \
        {ax: (n // total_dcn if ax == "data" else 1) for ax in MESH_AXES}
    ici_shape = tuple(ici[ax] for ax in MESH_AXES)
    dcn_shape = tuple(dcn[ax] for ax in MESH_AXES)
    total = int(np.prod(ici_shape)) * total_dcn
    devices = list(devices)[:total]
    if getattr(devices[0], "slice_index", None) is not None:
        # real multi-slice pod: slice membership is ground truth, and
        # any shape/topology mismatch must fail LOUDLY here — falling
        # back to a flat-list layout would silently put "ICI" neighbors
        # across DCN and tank every per-layer collective
        from jax.experimental import mesh_utils
        device_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices)
    else:
        # single-slice / CPU devices carry no slice attribution:
        # emulate the hybrid layout — DCN-major per axis, matching
        # create_hybrid_device_mesh's semantics (slice-local blocks
        # stay contiguous on every combined axis) — so CI exercises
        # the exact axis arithmetic a pod run uses
        arr = np.asarray(devices).reshape(dcn_shape + ici_shape)
        nd = len(MESH_AXES)
        perm = []
        for i in range(nd):
            perm += [i, nd + i]
        device_array = arr.transpose(perm).reshape(
            tuple(d * i for d, i in zip(dcn_shape, ici_shape)))
    return Mesh(device_array, MESH_AXES)


def single_device_mesh(device=None):
    device = device or jax.devices()[0]
    arr = np.asarray([device]).reshape((1,) * len(MESH_AXES))
    return Mesh(arr, MESH_AXES)

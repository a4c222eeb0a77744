"""Device meshes + sharding helpers.

The mesh is the TPU-native replacement for the reference's device lists
(layers/device.py:26 get_places, platform/Place) — instead of enumerating
CUDAPlaces and splitting work per place (parallel_do_op.cc:37
SplitTensorAndMoveTensorToScopes), a Mesh names logical axes ('dp' data,
'mp' model/tensor, 'sp' sequence) and sharding specs map tensor dims onto
them; XLA's SPMD partitioner does the splitting and inserts the collectives.
"""

from __future__ import annotations

import contextlib
import warnings
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..fluid.core.lod import SeqArray

__all__ = ["Mesh", "make_mesh", "set_mesh", "current_mesh", "mesh_guard",
           "feed_sharding", "state_sharding", "kernel_axes"]

_current_mesh: Optional[Mesh] = None


def make_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Build a named mesh, e.g. make_mesh({'dp': 4, 'mp': 2}).

    Axis order follows dict order; put the fastest-varying (most
    bandwidth-hungry, usually 'mp') axis LAST so it lands on the
    innermost ICI ring.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = int(np.prod(list(axes.values())))
    if n > len(devices):
        raise ValueError(f"mesh {axes} needs {n} devices, "
                         f"have {len(devices)}")
    dev = np.asarray(devices[:n]).reshape(tuple(axes.values()))
    return Mesh(dev, tuple(axes.keys()))


def set_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    global _current_mesh
    old, _current_mesh = _current_mesh, mesh
    return old


def current_mesh() -> Optional[Mesh]:
    return _current_mesh


@contextlib.contextmanager
def mesh_guard(mesh: Mesh):
    old = set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(old)


# batch-sharding axes: 'dp' (training) or 'batch' (the serving
# batch × model mesh)
_BATCH_AXES = ("dp", "batch")
# axes that never carry attention heads: batch, sequence, expert, stage
_NON_HEAD_AXES = _BATCH_AXES + ("sp", "ep", "pp")


def _dp_axes(mesh: Mesh):
    """Axes used for batch sharding, whichever is present, else none."""
    return [a for a in _BATCH_AXES if a in mesh.axis_names]


def kernel_axes(mesh: Mesh, batch: int,
                heads: int) -> Tuple[Optional[str], Optional[str]]:
    """(batch_axis, head_axis) a shard_map'd attention kernel splits
    over on ``mesh``: the data axis ('dp' / 'batch') when it divides
    ``batch``, and the tensor-parallel axis — 'mp' in training, 'model'
    (any other name) on the serving mesh — when it divides ``heads``.
    None for a dim no axis divides: shard_map needs even splits, so that
    dim stays whole on every device (what the partitioner does for the
    XLA path)."""
    dp = _dp_axes(mesh)
    b_ax = dp[0] if dp and batch % mesh.shape[dp[0]] == 0 else None
    h_ax = next((a for a in mesh.axis_names
                 if a not in _NON_HEAD_AXES and mesh.shape[a] > 1
                 and heads % mesh.shape[a] == 0), None)
    return b_ax, h_ax


def feed_sharding(mesh: Mesh, value):
    """Sharding tree for one feed value: batch (dim 0) over 'dp'.  A
    batch the axis does not divide cannot be split — it replicates, and
    says so: every device then computes the whole batch."""
    dp = _dp_axes(mesh)

    def leaf(v):
        # shape/dtype attrs only: np.asarray on a process-spanning global
        # jax.Array raises (non-addressable shards), and pre-sharded
        # device feeds are exactly the multi-host fast path
        s = getattr(v, "shape", None)   # () is a valid (0-d) shape — no `or`
        shape = tuple(s) if s is not None else np.asarray(v).shape
        if dp and len(shape) >= 1:
            n = mesh.shape[dp[0]]
            if shape[0] % n == 0:
                return NamedSharding(mesh, PartitionSpec(dp[0]))
            if shape[0] > 1:
                warnings.warn(
                    f"feed of shape {shape}: leading dim {shape[0]} is not "
                    f"divisible by mesh axis {dp[0]!r}={n}; the feed is "
                    f"REPLICATED and every device computes the whole "
                    f"batch — pad or resize the batch to a multiple of "
                    f"{n}", RuntimeWarning, stacklevel=3)
        return NamedSharding(mesh, PartitionSpec())

    if isinstance(value, SeqArray):
        return SeqArray(leaf(value.data), leaf(value.lengths))
    return leaf(value)


def state_sharding(mesh: Mesh, value, annotation: Optional[Sequence]):
    """Sharding for a persistable var from its VarDesc annotation (tuple of
    mesh-axis names or None per dim).  Unannotated or non-divisible dims
    replicate.  An entry ``"axis?"`` (e.g. ZeRO moment sharding, see
    optimizer._add_accumulator) is a deferred placement: it binds to the
    first dim divisible by the axis size — preferring the annotated dim —
    or drops out entirely if none divides."""
    def leaf(v, ann):
        s = getattr(v, "shape", None)   # () is a valid (0-d) shape — no `or`
        shape = tuple(s) if s is not None else np.asarray(v).shape
        ndim = len(shape)
        if not ann:
            return NamedSharding(mesh, PartitionSpec())
        ann = (list(ann) + [None] * ndim)[: ndim]
        spec = [None] * ndim
        deferred = []
        for i, (d, ax) in enumerate(zip(shape, ann)):
            if ax is None:
                continue
            if isinstance(ax, str) and ax.endswith("?"):
                deferred.append((i, ax[:-1]))
            elif ax in mesh.axis_names and d % mesh.shape[ax] == 0:
                spec[i] = ax
        for i, ax in deferred:
            if ax not in mesh.axis_names or ax in spec:
                continue
            size = mesh.shape[ax]
            for j in [i] + [k for k in range(ndim) if k != i]:
                if spec[j] is None and shape[j] % size == 0:
                    spec[j] = ax
                    break
        return NamedSharding(mesh, PartitionSpec(*spec))

    if isinstance(value, SeqArray):
        return SeqArray(leaf(value.data, annotation),
                        NamedSharding(mesh, PartitionSpec()))
    return leaf(value, annotation)

"""Production mesh factory.

A function (not a module constant) so importing never touches jax device
state.  Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2 pods x
256 = 512 chips with the leading "pod" axis (DP across pods by default;
runtime/pipeline.py can pipeline over it instead).

Every mesh here has Auto axes: the sharding of intermediates is left to
the XLA partitioner, and the code states shardings only at its inputs
and outputs (``jax.make_mesh`` defaults to Explicit axes, under which
gathers and scatters such as the embedding lookup or overlap-add need a
sharding spelled out for every operand)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_data_mesh(n_devices=None):
    """1-D data-parallel mesh over the local devices — the serving
    stack's mesh (`SignalMesh` shards bucket batches and stream-session
    blocks over its single ``data`` axis)."""
    n = int(n_devices) if n_devices else len(jax.devices())
    return auto_mesh((n,), ("data",))

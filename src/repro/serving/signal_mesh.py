"""SigMesh: the data-parallel execution domain of a sharded
:class:`~repro.serving.signal_service.SignalService`.

Two pieces, deliberately separable:

  * :class:`SignalMesh` — the *placement* layer.  Wraps a 1-D jax mesh
    over the ``data`` axis (:func:`repro.launch.mesh.make_data_mesh`)
    and turns bucket batches into row-sharded device arrays via
    :class:`jax.sharding.NamedSharding`
    (:func:`repro.models.sharding.batch_spec` builds the spec, so the
    serving path follows the exact same degrade-to-replicate rules as
    training batches).  Row counts pad up to a multiple of the
    **logical shard count** with zero rows — every compiled graph is
    row-independent (batched einsums over per-row suffix axes), so pad
    rows compute garbage that is simply never read back, and the
    real rows' values equal the unsharded execution's up to float32
    rounding (per-device programs see fewer rows, and XLA does not
    promise one reduction order across shapes).
    ``n_shards`` may exceed the physical device count (shards then
    co-locate, wrapping round-robin over the devices) — that keeps the
    routing / occupancy / affinity logic testable in a single-device
    process while the forced-8-device subprocess tests exercise real
    placement.
  * :class:`DeviceRouter` — the *accounting* layer, pure host-side
    state.  Least-loaded assignment of streaming sessions to shard
    indices (device affinity: a session's carried ``StreamState``
    stays on its shard across ticks), a per-shard cycle ledger fed by
    the perf model (:func:`repro.core.perf_model.device_step_costs`),
    and liveness flags so a dropped device stops receiving work.

Neither piece touches request payloads; sharded serving against the
unsharded service is tested in tests/test_signal_mesh_faults.py on
forced 4- and 8-device host meshes.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SignalMesh", "DeviceRouter", "trim_rows"]


class SignalMesh:
    """Data-parallel placement for :class:`SignalService`.

    ``n_shards`` is the logical data-parallel width (default: the
    number of visible jax devices).  The underlying jax mesh spans
    ``min(n_shards, len(jax.devices()))`` devices on one ``data``
    axis; when ``n_shards`` exceeds the physical count, shards wrap
    over the devices (placement degrades, the math does not).
    """

    def __init__(self, n_shards: Optional[int] = None, mesh=None):
        devices = jax.devices()
        if mesh is not None:
            self.mesh = mesh
            self.devices = list(mesh.devices.flat)
            self.n_shards = int(n_shards or len(self.devices))
        else:
            self.n_shards = int(n_shards or len(devices))
            if self.n_shards < 1:
                raise ValueError("n_shards must be >= 1")
            from ..launch.mesh import make_data_mesh
            self.mesh = make_data_mesh(min(self.n_shards, len(devices)))
            self.devices = list(self.mesh.devices.flat)

    @classmethod
    def coerce(cls, mesh) -> Optional["SignalMesh"]:
        """``None`` | ``SignalMesh`` | shard count | jax ``Mesh`` ->
        ``SignalMesh`` (or None).  The service constructor's adapter."""
        if mesh is None or isinstance(mesh, cls):
            return mesh
        if isinstance(mesh, int):
            return cls(n_shards=mesh)
        return cls(mesh=mesh)           # a jax Mesh

    # -- bucket-batch sharding ---------------------------------------------
    def padded_rows(self, rows: int) -> int:
        """Rows after padding up to a multiple of the shard count (the
        even split NamedSharding row-partitioning needs)."""
        return max(1, math.ceil(rows / self.n_shards)) * self.n_shards

    def align_row_budget(self, budget: Optional[int]) -> Optional[int]:
        """A scheduler row budget rounded UP to a shard multiple (and
        never below one full shard round).  Splitting a wave at a
        non-multiple chunk size would add zero pad rows to EVERY chunk
        — each shard would spend cycles computing padding on every
        tick — so the preemptible scheduler aligns its chunks to the
        shard width and pays the row padding at most once, on the
        remainder chunk."""
        if budget is None:
            return None
        return self.padded_rows(max(1, int(budget)))

    def row_sharding(self, shape) -> jax.sharding.NamedSharding:
        """NamedSharding splitting the leading (batch) axis over the
        mesh's data axes; replicates if the row count does not divide
        (same degrade rules as training batches)."""
        from ..models.sharding import row_sharding
        return row_sharding(self.mesh, shape)

    def shard(self, arr) -> jax.Array:
        """Place a (rows-padded) batch row-sharded over the mesh."""
        arr = jnp.asarray(arr)
        return jax.device_put(arr, self.row_sharding(arr.shape))

    def row_parallel(self, fn, n_rows: int):
        """Jitted ``fn(*rows, params)`` for row-sharded batches: the
        ``n_rows`` leading arguments and every output split by row over
        the mesh's batch axes, ``params`` replicated.  It runs as a
        ``shard_map`` — each device computes its own rows — because XLA
        cannot partition a Pallas (Mosaic) kernel, and the row math is
        independent, so per-shard execution is exact.  Batches whose
        rows do not split evenly (shards wrapped onto fewer devices) or a
        one-device mesh take the plain jit on the replicated batch."""
        from jax.sharding import PartitionSpec as P
        from ..models.sharding import batch_axes, mesh_axes_of
        sizes = mesh_axes_of(self.mesh)
        axes = batch_axes(sizes)
        n_dev = math.prod(sizes[a] for a in axes)
        whole = jax.jit(fn)
        if n_dev < 2:
            return whole
        spec = P(axes if len(axes) > 1 else axes[0])
        split = jax.jit(jax.shard_map(
            fn, mesh=self.mesh, in_specs=(spec,) * n_rows + (P(),),
            out_specs=spec, check_vma=False))

        def call(*args):
            return (split if args[0].shape[0] % n_dev == 0 else whole)(*args)
        return call

    # -- streaming-session affinity ----------------------------------------
    def device_for(self, shard_index: int):
        """The physical device backing a logical shard index (shards
        beyond the physical count wrap round-robin)."""
        return self.devices[shard_index % len(self.devices)]

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"SignalMesh(n_shards={self.n_shards}, "
                f"devices={len(self.devices)})")


class DeviceRouter:
    """Host-side shard router + per-device occupancy ledger.

    ``assign()`` picks the least-loaded *alive* shard (stable
    tie-break: lowest index) — the service calls it once per
    ``open_stream``, giving the session device affinity for life;
    ``charge()`` accumulates perf-model cycles per shard as work
    executes.  ``drop()`` marks a shard dead (simulated device loss):
    it stops receiving assignments and the service re-homes its
    sessions.  Everything is plain ints, so routing properties are
    testable without any multi-device runtime.
    """

    def __init__(self, n_devices: int):
        if n_devices < 1:
            raise ValueError("n_devices must be >= 1")
        self.n_devices = int(n_devices)
        self.device_cycles: List[int] = [0] * self.n_devices
        self.device_sessions: List[int] = [0] * self.n_devices
        self.alive: List[bool] = [True] * self.n_devices

    def assign(self, cost_hint: int = 0) -> int:
        """Least-loaded alive shard — fewest assigned sessions first
        (so a burst of opens spreads before any work runs), then fewest
        spent cycles, then lowest index.  ``cost_hint`` (optional)
        charges the expected cost at assignment time."""
        alive = [i for i in range(self.n_devices) if self.alive[i]]
        if not alive:
            raise RuntimeError("no alive devices to assign to")
        idx = min(alive, key=lambda i: (self.device_sessions[i],
                                        self.device_cycles[i], i))
        self.device_sessions[idx] += 1
        if cost_hint:
            self.device_cycles[idx] += int(cost_hint)
        return idx

    def release(self, index: Optional[int]) -> None:
        """A session left its shard (closed or re-homed)."""
        if index is not None and self.device_sessions[index] > 0:
            self.device_sessions[index] -= 1

    def charge(self, index: int, cycles: int) -> None:
        self.device_cycles[index] += int(cycles)

    def drop(self, index: int) -> None:
        """Mark a shard dead.  Its ledger survives (the cycles were
        really spent); it just stops receiving work."""
        self.alive[index] = False

    def alive_count(self) -> int:
        return sum(self.alive)

    def occupancy(self) -> Dict:
        """Per-device cycle shares — the per-device counterpart of
        ``CoScheduler.occupancy()``."""
        total = sum(self.device_cycles)
        return {
            "device_cycles": list(self.device_cycles),
            "device_share": [c / total if total else 0.0
                             for c in self.device_cycles],
            "sessions": list(self.device_sessions),
            "alive": list(self.alive),
            "total_cycles": total,
        }


def trim_rows(out, rows: int):
    """Drop pad rows from a (possibly multi-output) batched result —
    the inverse of :meth:`SignalMesh.padded_rows` padding."""
    return jax.tree_util.tree_map(lambda a: a[:rows], out)

"""Sharding policy: the one object that decides how the partitioned
engine lays state and batches over devices (r14).

The paper's thesis maps Gubernator's consistent-hash ring onto mesh
axes; before r14 that mapping was smeared across three engine variants
(TpuEngine / MeshEngine / MultiHostMeshEngine) whose decide/upsert/
snapshot paths could drift independently — and did: the mesh variants
sat unverified in the permanent failure set. A `ShardingPolicy` now
carries everything topology-specific — devices, mesh axes, the
NamedSharding specs for store rows and request columns, the collective
choice for GLOBAL sync — and ONE engine (parallel/sharded.py
PartitionedEngine) consumes it, with the single-device policy as the
degenerate case (no mesh, flat [B] batches, plain jit: byte-identical
to the historical TpuEngine fast path).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class ShardingPolicy:
    """How one engine's state and batches map onto devices.

    - `devices`: the shards, in shard-index order (None on the flat
      single-device policy, where placement is jax's default or the
      one pinned `device`).
    - `axes`: mesh axis names, host-major — ("shard",) flat 1-D, or
      ("host", "chip") when the reduction should stage ICI-then-DCN
      (BASELINE config 5's hierarchical psum). Empty for single.
    - `mesh`: the jax Mesh (None => no mesh: the degenerate policy).
    - `spans_processes`: True when the mesh crosses process boundaries
      (multi-controller SPMD): responses must all_gather back to the
      serving leader, and host-side state reads (snapshot/sketch
      gathers for replication and the promoter) are unavailable — the
      follower processes would have to issue matching programs.
    """

    device: Optional[jax.Device] = None
    devices: Optional[Tuple[jax.Device, ...]] = None
    axes: Tuple[str, ...] = ()
    mesh: Optional[Mesh] = field(default=None, compare=False)
    spans_processes: bool = False

    # -- factories ----------------------------------------------------------

    @classmethod
    def single(cls, device: Optional[jax.Device] = None) -> "ShardingPolicy":
        """The degenerate policy: one shard, no mesh, flat [B] batches,
        plain jit dispatch — the historical TpuEngine layout."""
        return cls(device=device)

    @classmethod
    def over_mesh(
        cls,
        devices: Optional[Sequence[jax.Device]] = None,
        mesh_shape: Optional[Tuple[int, int]] = None,
    ) -> "ShardingPolicy":
        """Key-space sharding over a device mesh. `mesh_shape` forces a
        2-D ("host", "chip") layout; by default a multi-process device
        list with equal per-process counts auto-selects it, after
        validating each reshaped row is single-process (else the
        "ICI within a row, DCN across rows" staging would silently
        cross DCN inside a row — ADVICE r5 #1) — the flat ("shard",)
        mesh is the fallback."""
        if devices is None:
            devices = jax.devices()
        devices = tuple(devices)
        n = len(devices)
        procs = {d.process_index for d in devices}
        span = len(procs) > 1
        if mesh_shape is None and span and n % len(procs) == 0:
            grid = np.asarray(devices).reshape(len(procs), n // len(procs))
            if all(
                len({d.process_index for d in row}) == 1 for row in grid
            ):
                mesh_shape = (len(procs), n // len(procs))
        if mesh_shape is not None:
            n_hosts, per_host = mesh_shape
            if n_hosts * per_host != n:
                raise ValueError(
                    f"mesh_shape {mesh_shape} != {n} devices"
                )
            mesh = Mesh(
                np.asarray(devices).reshape(n_hosts, per_host),
                ("host", "chip"),
            )
            axes: Tuple[str, ...] = ("host", "chip")
        else:
            mesh = Mesh(np.asarray(devices), ("shard",))
            axes = ("shard",)
        return cls(
            devices=devices, axes=axes, mesh=mesh, spans_processes=span
        )

    # -- derived properties -------------------------------------------------

    @property
    def flat(self) -> bool:
        """True for the degenerate single-device policy."""
        return self.mesh is None

    @property
    def n_shards(self) -> int:
        return 1 if self.flat else len(self.devices)

    @property
    def hierarchical(self) -> bool:
        """Stage the GLOBAL-sync reduction ICI-then-DCN (2-D mesh)."""
        return len(self.axes) > 1

    def store_spec(self) -> P:
        """PartitionSpec for state rows: leading shard axis over every
        mesh axis, host-major (store [n_shards, buckets, W], sketch
        [n_shards, rows, width])."""
        assert not self.flat
        return P(self.axes)

    def request_spec(self) -> P:
        """PartitionSpec for request columns: per-shard sub-batches
        [n_shards, B_sub] laid over the same axes as the store, so row
        s of every field sits on the chip owning key-space shard s."""
        return self.store_spec()

    def replicated_spec(self) -> P:
        return P()

    def store_sharding(self) -> NamedSharding:
        assert not self.flat
        return NamedSharding(self.mesh, self.store_spec())

    def describe(self) -> str:
        if self.flat:
            return "single-device (flat, degenerate policy)"
        shape = dict(self.mesh.shape)
        return (
            f"{self.n_shards}-shard mesh {shape} axes={self.axes} "
            f"collective={'hierarchical' if self.hierarchical else 'flat'}"
            f"{' multi-process' if self.spans_processes else ''}"
        )

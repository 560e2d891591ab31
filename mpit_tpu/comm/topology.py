"""Topology bootstrap: the TPU-native ``mpiT.Init / Comm_rank / Comm_size``.

Reference parity (SURVEY.md §3(a), BASELINE.json:5): ``mpirun`` spawned N Lua
processes which called ``mpiT.Init()`` then discovered ``rank``/``size`` from
``MPI_COMM_WORLD``. Here the "world" is the TPU slice: processes bootstrap via
``jax.distributed`` (when launched multi-host), devices are discovered from
the slice, and the worker axis of the job is a ``jax.sharding.Mesh`` axis —
one *device* per worker, rather than one OS process per worker, because on TPU
the unit of compute is the chip and collectives ride ICI between chips.

Two notions of identity therefore coexist and both are exposed:

- ``process_rank()`` / ``process_count()`` — host-process identity
  (``jax.process_index/count``); the moral equivalent of an MPI rank for
  host-side work (logging, data sharding, the host-async PS transport).
- ``rank()`` / ``size()`` — *worker* identity: position along the mesh's
  worker ("dp") axis. Inside jit/shard_map this is ``lax.axis_index``.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax
import numpy as np

from mpit_tpu.analysis.runtime import make_lock
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Default mesh axis for the data-parallel worker dimension. The reference's
# only parallelism is data parallelism in three flavors (SURVEY.md §2
# parallelism-strategy ledger), so a 1-D mesh is the common case.
WORKER_AXIS = "dp"

_lock = make_lock("topology._lock")
_topology: Optional["Topology"] = None
_distributed_initialized = False


@dataclasses.dataclass(frozen=True)
class Topology:
    """World description produced by :func:`init`.

    Attributes:
      mesh: the global device mesh; axis ``axis_names[0]`` (default ``"dp"``)
        is the worker axis used by the trainers.
      devices: all addressable-or-not global devices, mesh order.
      process_index / process_count: host-process identity.
    """

    mesh: Mesh
    devices: tuple
    process_index: int
    process_count: int
    platform: str

    @property
    def num_workers(self) -> int:
        """Length of the worker axis (what ``size()``/collectives reduce over).

        On a multi-axis mesh this is NOT the total device count — see
        :attr:`num_devices`.
        """
        return int(self.mesh.devices.shape[0])

    @property
    def num_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    @property
    def worker_axis(self) -> str:
        return self.mesh.axis_names[0]

    @property
    def local_devices(self) -> tuple:
        return tuple(d for d in self.devices if d.process_index == self.process_index)

    def worker_sharding(self, *trailing_axes: Optional[str]) -> NamedSharding:
        """NamedSharding that shards the leading axis across workers."""
        return NamedSharding(
            self.mesh, PartitionSpec(self.worker_axis, *trailing_axes)
        )

    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())


def _should_init_distributed() -> bool:
    """Multi-host bootstrap is opt-in via standard jax env vars.

    On a single host calling
    ``jax.distributed.initialize`` without a coordinator either fails or
    hangs, so only do it when the launcher says so — mirroring how the
    reference only had a world when run under ``mpirun`` (SURVEY.md §3(a)).
    """
    if os.environ.get("MPIT_DISTRIBUTED", "").lower() in ("1", "true"):
        return True
    return bool(os.environ.get("JAX_COORDINATOR_ADDRESS"))


def _init_distributed() -> None:
    """Bootstrap ``jax.distributed`` from the launch environment.

    On managed clusters (TPU pods, SLURM) the no-arg form auto-detects.
    Under this repo's own launcher — ``python -m mpit_tpu.launch -n N
    --jax-distributed`` — the world is described by the same env contract
    the PS transport uses (``MPIT_RANK``/``MPIT_WORLD_SIZE``) plus
    ``JAX_COORDINATOR_ADDRESS``, and this jax build does not read
    process-count/id from env, so pass them explicitly.
    """
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = os.environ.get("MPIT_WORLD_SIZE")
    pid = os.environ.get("MPIT_RANK")
    if coord and nproc is not None and pid is not None:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(nproc),
            process_id=int(pid),
        )
    else:
        jax.distributed.initialize()


def init(
    axis_names: Sequence[str] = (WORKER_AXIS,),
    mesh_shape: Optional[Sequence[int]] = None,
    num_workers: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> Topology:
    """Initialize the world: ``mpiT.Init()`` ≡ topology discovery + mesh.

    Args:
      axis_names: mesh axis names; first is the worker axis.
      mesh_shape: explicit mesh shape (must multiply to #devices used).
      num_workers: use only the first ``num_workers`` devices on a 1-D mesh
        (handy for carving a sub-world, like an MPI sub-communicator).
      devices: explicit device list (tests).

    Idempotent: repeated calls return the existing topology unless
    :func:`finalize` ran in between.
    """
    global _topology, _distributed_initialized
    with _lock:
        if _topology is not None:
            explicit = (
                tuple(axis_names) != (WORKER_AXIS,)
                or mesh_shape is not None
                or num_workers is not None
                or devices is not None
            )
            if explicit:
                raise RuntimeError(
                    "mpit_tpu.init() called with explicit arguments but a "
                    "topology already exists (possibly auto-created); call "
                    "finalize() first to rebuild the world"
                )
            return _topology

        if _should_init_distributed() and not _distributed_initialized:
            _init_distributed()
            _distributed_initialized = True

        devs = list(devices if devices is not None else jax.devices())
        if num_workers is not None:
            if num_workers > len(devs):
                raise ValueError(
                    f"num_workers={num_workers} exceeds available devices "
                    f"({len(devs)})"
                )
            devs = devs[:num_workers]

        if mesh_shape is None:
            mesh_shape = (len(devs),) + (1,) * (len(axis_names) - 1)
        if int(np.prod(mesh_shape)) != len(devs):
            raise ValueError(
                f"mesh_shape {tuple(mesh_shape)} does not cover {len(devs)} devices"
            )
        mesh = Mesh(
            np.asarray(devs, dtype=object).reshape(tuple(mesh_shape)),
            axis_names=tuple(axis_names),
        )
        _topology = Topology(
            mesh=mesh,
            devices=tuple(devs),
            process_index=jax.process_index(),
            process_count=jax.process_count(),
            platform=devs[0].platform if devs else "none",
        )
        return _topology


def finalize() -> None:
    """``mpiT.Finalize()``: drop the world. Safe to call when uninitialized.

    In multi-host mode this also shuts down the ``jax.distributed`` client so
    a later :func:`init` can bootstrap again; single-host it only drops the
    mesh (XLA needs no collective teardown).
    """
    global _topology, _distributed_initialized
    with _lock:
        _topology = None
        if _distributed_initialized:
            jax.distributed.shutdown()
            _distributed_initialized = False


def is_initialized() -> bool:
    return _topology is not None


def topology() -> Topology:
    """The current topology, auto-initializing with defaults if needed."""
    if _topology is None:
        return init()
    return _topology


def process_rank() -> int:
    """Host-process index (≡ MPI rank of the host in multi-host jobs)."""
    return topology().process_index


def process_count() -> int:
    return topology().process_count


def rank():
    """Worker id. Inside jit/shard_map: a traced ``lax.axis_index`` over the
    worker axis. Outside a tracing context this raises — host code should use
    :func:`process_rank` (there is no single "my device" outside SPMD).
    """
    return jax.lax.axis_index(topology().worker_axis)


def size() -> int:
    """Number of workers (devices on the worker axis) — ``mpiT.Comm_size``."""
    return topology().num_workers


# ---------------------------------------------------------------------------
# Consistent-hash shard ring (sharded parameter servers).
#
# Ownership of parameter shards is decided by a consistent-hash ring over the
# live server ranks (docs/ROBUSTNESS.md "Shard ownership & resharding"). The
# ring is deterministic across processes — keys are hashed with blake2b, never
# Python's randomized ``hash()`` — so every client and server derives the same
# assignment from the same member set without coordination. Removing one of N
# members moves only the shards the leaver owned (~1/N of keys); everything
# else stays put, which is what bounds reshard traffic under churn.


def _ring_hash(key: str) -> int:
    import hashlib

    return int.from_bytes(
        hashlib.blake2b(key.encode("utf-8"), digest_size=8).digest(), "big"
    )


class HashRing:
    """Consistent-hash ring over server ranks with a monotonic version.

    ``version`` increments on every membership change (``without`` /
    ``with_member``) and rides the TAG_SHARD_MAP wire envelope so receivers
    can discard stale views. Instances are immutable; membership edits return
    a new ring.
    """

    __slots__ = ("members", "vnodes", "version", "_points")

    def __init__(self, members, vnodes: int = 64, version: int = 0):
        self.members = tuple(sorted(set(int(m) for m in members)))
        if not self.members:
            raise ValueError("HashRing needs at least one member")
        self.vnodes = int(vnodes)
        self.version = int(version)
        pts = []
        for m in self.members:
            for v in range(self.vnodes):
                pts.append((_ring_hash(f"m{m}:v{v}"), m))
        pts.sort()
        self._points = pts

    def owner(self, key) -> int:
        """The member owning ``key`` (first point clockwise of its hash)."""
        import bisect

        h = _ring_hash(f"k{key}")
        i = bisect.bisect_right(self._points, (h, 1 << 62))
        if i == len(self._points):
            i = 0
        return self._points[i][1]

    def without(self, rank: int) -> "HashRing":
        rest = [m for m in self.members if m != rank]
        return HashRing(rest, vnodes=self.vnodes, version=self.version + 1)

    def with_member(self, rank: int) -> "HashRing":
        return HashRing(
            self.members + (int(rank),), vnodes=self.vnodes, version=self.version + 1
        )

    def __eq__(self, other):
        return (
            isinstance(other, HashRing)
            and self.members == other.members
            and self.vnodes == other.vnodes
        )

    def __hash__(self):
        return hash((self.members, self.vnodes))

    def __repr__(self):
        return f"HashRing(members={self.members}, vnodes={self.vnodes}, version={self.version})"


def shard_layout(param_size: int, num_shards: int):
    """Static, contiguous, near-equal split of the flat parameter vector.

    The layout never changes across membership churn — only *ownership* of
    each shard moves. Mirrors ``pserver.partition_bounds`` (kept separate to
    avoid a comm→parallel import cycle).
    """
    if num_shards <= 0:
        raise ValueError("num_shards must be positive")
    base, extra = divmod(param_size, num_shards)
    bounds = []
    start = 0
    for i in range(num_shards):
        size = base + (1 if i < extra else 0)
        bounds.append((start, start + size))
        start += size
    return bounds


class ShardMap:
    """Ring + static layout glue: who owns which slice of the flat params.

    ``assignment[sid]`` is the owning rank of shard ``sid``; the slice bounds
    come from :func:`shard_layout` and are immutable — a reshard moves
    ownership, never the cut points.
    """

    __slots__ = ("ring", "param_size", "num_shards", "layout", "assignment")

    def __init__(self, ring: HashRing, param_size: int, num_shards: int):
        self.ring = ring
        self.param_size = int(param_size)
        self.num_shards = int(num_shards)
        self.layout = shard_layout(self.param_size, self.num_shards)
        self.assignment = tuple(ring.owner(sid) for sid in range(self.num_shards))

    def with_ring(self, ring: HashRing) -> "ShardMap":
        return ShardMap(ring, self.param_size, self.num_shards)

    def ranges_for(self, rank: int):
        """Ascending ``(sid, start, end)`` triples owned by ``rank``."""
        return [
            (sid, s, e)
            for sid, (s, e) in enumerate(self.layout)
            if self.assignment[sid] == rank
        ]

    def owned_size(self, rank: int) -> int:
        return sum(e - s for _, s, e in self.ranges_for(rank))

    def server_ranks(self):
        """Members that own at least one shard, ascending."""
        return sorted(set(self.assignment))

    def shard_size(self, sid: int) -> int:
        s, e = self.layout[sid]
        return e - s


def reshard_schedule(old_map: ShardMap, new_map: ShardMap):
    """The slice exchanges needed to go from ``old_map`` to ``new_map``.

    Returns ascending-shard-id moves ``{"shard", "src", "dst", "size"}``.
    Executed in order, each destination holds at most its old slices plus the
    one incoming slice at any instant (see :func:`schedule_peak_elems`) — the
    no-full-duplicate property from the portable-redistribution literature.
    """
    if old_map.param_size != new_map.param_size or old_map.num_shards != new_map.num_shards:
        raise ValueError("reshard requires identical layout on both sides")
    moves = []
    for sid in range(old_map.num_shards):
        src = old_map.assignment[sid]
        dst = new_map.assignment[sid]
        if src != dst:
            moves.append(
                {"shard": sid, "src": src, "dst": dst, "size": old_map.shard_size(sid)}
            )
    return moves


def schedule_peak_elems(moves, old_map: ShardMap):
    """Per-rank peak resident element count while executing ``moves`` in order.

    A destination materializes the incoming slice while the source still holds
    it (the transfer), then the source frees its copy. The peak for every rank
    must stay ≤ old resident + incoming — never the full model.
    """
    ranks = set(old_map.ring.members)
    for mv in moves:
        ranks.add(mv["src"])
        ranks.add(mv["dst"])
    resident = {r: old_map.owned_size(r) for r in ranks}
    peak = dict(resident)
    for mv in moves:
        src, dst, size = mv["src"], mv["dst"], mv["size"]
        resident[dst] += size
        peak[dst] = max(peak[dst], resident[dst])
        resident[src] -= size
    return peak

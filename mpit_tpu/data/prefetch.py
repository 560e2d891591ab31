"""Device-prefetching input pipeline.

The reference fed Torch tensors from host RAM synchronously inside its
training loop (SURVEY.md §2 comp. 8) — fine for a CPU-bound Lua harness,
but on TPU a synchronous host→device copy in the step path serializes the
host-link transfer with the compute. The TPU-native pattern is to stage
upcoming batches into HBM *while the current step runs*: ``jax.device_put``
is asynchronous (it returns immediately and the transfer proceeds in the
background), so holding a small deque of already-dispatched batches ahead
of the consumer overlaps transfer with compute at zero thread cost.

Staging uses the step's own input sharding (leading worker axis) — a default
``device_put`` would commit to device 0 and push a redistribute back into
every step (the same trap bench.py's staging avoids).
"""

from __future__ import annotations

from collections import deque
from typing import Any, Callable, Iterable, Iterator, Optional

import jax

from mpit_tpu.utils.profiling import span


def prefetch_to_device(
    it: Iterable[Any],
    sharding,
    depth: int = 2,
    first_unit: int = 1,
) -> Iterator[Any]:
    """Yield items of ``it`` (pytrees of host arrays) staged on device.

    ``depth`` batches are dispatched ahead of the consumer; ``depth=0``
    degrades to synchronous per-item staging. The sharding is applied to
    every array leaf. Each staged item costs its full HBM footprint until
    consumed — peak input memory is ``depth + 1`` items.

    Each staging is a ``mpit.fit.stage`` span whose ``unit`` counts from
    ``first_unit``: the number of the fit-loop unit the item is for, which
    runs ``depth`` units after the staging.
    """
    if depth < 0:  # validate eagerly, not at first next()
        raise ValueError(f"depth must be >= 0, got {depth}")
    return _prefetch_gen(it, sharding, depth, first_unit)


def _prefetch_gen(it, sharding, depth, first_unit) -> Iterator[Any]:
    buf: deque = deque()
    for unit, item in enumerate(it, first_unit):
        # device_put maps one sharding over every leaf of a pytree itself
        with span("mpit.fit.stage", unit=unit):
            staged = jax.device_put(item, sharding)
        buf.append(staged)
        if len(buf) > depth:
            yield buf.popleft()
    while buf:
        yield buf.popleft()


class DeviceBatches:
    """A :class:`~mpit_tpu.data.Batches`-shaped epoch iterator whose batches
    arrive already sharded onto the worker mesh axis, ``depth`` ahead.

    Wraps any object with ``epoch(i)`` / ``steps_per_epoch()`` (the Batches
    protocol). An optional ``transform(x, y) -> item`` reshapes each host
    batch before staging (e.g. a τ-round regrouping); by default items are
    the ``(x, y)`` pairs unchanged.
    """

    def __init__(
        self,
        batches,
        topo,
        depth: int = 2,
        transform: Optional[Callable] = None,
    ):
        if depth < 0:
            raise ValueError(f"depth must be >= 0, got {depth}")
        self.batches = batches
        self.topo = topo
        self.depth = int(depth)
        self.transform = transform

    def steps_per_epoch(self) -> int:
        return self.batches.steps_per_epoch()

    def epoch(self, epoch_index: int) -> Iterator[Any]:
        sharding = self.topo.worker_sharding()
        it = self.batches.epoch(epoch_index)
        if self.transform is not None:
            it = (self.transform(x, y) for x, y in it)
        return prefetch_to_device(it, sharding, depth=self.depth)

"""Tracing / profiling hooks.

Reference parity (SURVEY.md §5): the reference had only ad-hoc tic/toc timers
and prints. TPU plan from the survey: ``jax.profiler`` trace hooks plus
per-step wall-clock counters — a captured trace opens in
Perfetto/TensorBoard and shows the XLA op timeline, ICI collectives
included, which is the observability the MPI version never had.

``span`` is the package's one way to name host work (the fit loops, the
prefetcher, ``Batches`` and ``init_state`` use it; docs/OBSERVABILITY.md has
the names); ``trace`` captures a profile; ``unit_program_text`` is where a
trace's device events get their ``jax.named_scope`` from.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Iterator, Optional

import jax

#: durations kept a span name (the newest; count/total/max cover them all)
RING = 4096


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax profiler trace into ``log_dir`` (no-op when None), so
    call sites can unconditionally wrap their hot loop."""
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield


class _Record:
    """What the registry keeps for one span name."""

    __slots__ = ("count", "total_s", "max_s", "ring")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self.ring = collections.deque(maxlen=RING)


# name -> _Record. No lock: a name has one writer (the thread that runs
# ``fit`` or set-up), and a reader takes ``snapshot()`` between units.
_registry: dict[str, _Record] = {}


class span:
    """``with span("mpit.fit.dispatch", unit=k):`` names a piece of host work.

    It enters a ``jax.profiler.TraceAnnotation(name, **ids)``, so that while
    a profiler runs the span lies in its trace on the clock of the device
    planes (with ``ids`` as the event's stats), and costs a flag test while
    none does; and it adds its ``time.perf_counter()`` duration to the
    module's registry, which ``snapshot()`` returns. Always on.

    A span never encloses a ``yield``: in a generator it wraps the work and
    closes before the value is handed out, or it would time the consumer."""

    __slots__ = ("name", "_annotation", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self._annotation = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        record = _registry.get(self.name)
        if record is None:
            record = _registry[self.name] = _Record()
        record.count += 1
        record.total_s += seconds
        if seconds > record.max_s:
            record.max_s = seconds
        record.ring.append(seconds)


def snapshot() -> dict:
    """The registry as plain data: per span name ``count``, ``total_s``,
    ``max_s`` and ``last_s``, the newest ``RING`` durations, oldest first."""
    return {
        name: {
            "count": r.count,
            "total_s": r.total_s,
            "max_s": r.max_s,
            "last_s": list(r.ring),
        }
        for name, r in _registry.items()
    }


def reset() -> None:
    global _unit, _unit_text
    _registry.clear()
    _unit = _unit_text = None


# (jitted program, its arguments as shapes) of the unit the newest fit loop
# dispatches: what ``unit_program_text`` compiles again
_unit = None
# its compiled text, once asked for: a second reader does not compile again
_unit_text = None


def remember_unit(program, *args) -> None:
    """Called by a fit loop after its first dispatch (it takes a few ms for
    a state of hundreds of leaves, which a device at work hides), with the
    unit's program and the arguments of its next call. Only shapes, dtypes
    and shardings are kept, so no buffer outlives its donation; the program
    (and the trainer it closes over) stays referenced until the next fit
    loop or ``reset()``."""
    global _unit, _unit_text
    _unit_text = None
    _unit = (
        program,
        jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding
            )
            if isinstance(a, jax.Array)
            else a,
            args,
        ),
    )


def unit_program_text() -> Optional[str]:
    """The compiled text of the unit program the newest fit loop ran, or
    None where none ran or the unit is a plain function, no jitted
    program. Its ``metadata={op_name="..."}`` is where an instruction's
    ``jax.named_scope`` path is found: the v5e's profiler trace names a
    device event by its instruction and carries no ``op_name`` (PERF.md).

    Compiled afresh, past two caches. The persistent compile cache's key
    leaves metadata out, so an executable cached by another revision of the
    source (the same arithmetic under other scopes) carries that revision's
    names, in its text and in a profile alike; and ``Lowered.compile``
    hands back the executable the fit loop's own call made (which may have
    come from that cache) unless it is given compiler options, so it is
    given one at its default value. The instructions are the same either
    way; only a fresh compile names them as this source does. That costs the
    unit's whole compile time (27 s for a GPT-2-small round on a v5e): call
    it after a measured window, never inside one."""
    global _unit_text
    if _unit is None or not hasattr(_unit[0], "lower"):
        return None
    if _unit_text is not None:
        return _unit_text
    from jax.experimental.compilation_cache import compilation_cache

    program, args = _unit
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # the flag is read once a cache's life
    try:
        _unit_text = (
            program.lower(*args)
            .compile(compiler_options={"xla_dump_hlo_as_text": False})
            .as_text()
        )
        return _unit_text
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def force_completion(*results) -> float:  # mpit-analysis: host-sync-barrier
    """Completion barrier by data dependence: fetch one host scalar that
    depends on every argument's outputs.

    What was found on the TPU v5e (PERF.md, PR 21): ``jax.block_until_ready``
    is honest there. A LeNet EASGD round (tau=4) timed over 200-round legs
    took 1441.5 us with ``block_until_ready`` and 1443.4 us with this
    function at 256 samples a chip, 22605 us and 22607 us at 4096 — the two
    barriers agree to 0.1% and both scale with the batch (15.7x for 16x).
    Used after every round instead of once per leg, this function costs
    about 0.2 ms more per call than ``block_until_ready`` (its reduction
    program and the fetch). It was written for an earlier platform on which
    ``block_until_ready`` returned early; the call sites still use it, and
    which barrier the benchmark keeps is ROADMAP Queue 1 item 1.

    For EACH positional argument, the smallest floating-point leaf is
    reduced; the per-argument scalars are fused into ONE device scalar and
    fetched with a single transfer. Pass the step's state and metrics as
    SEPARATE arguments so each gets its own proof leaf — a single pytree's
    smallest leaf is usually a loss scalar, which alone would not prove the
    state update finished. Non-floating leaves (ints, PRNG keys) are
    skipped; an argument with no floating leaf falls back to
    ``block_until_ready``.
    """
    import jax.numpy as jnp

    total = None
    for result in results:
        leaves = [
            leaf
            for leaf in jax.tree.leaves(result)
            if hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
        ]
        if not leaves:
            jax.block_until_ready(result)
            continue
        small = min(leaves, key=lambda leaf: leaf.size)
        term = jnp.sum(small).astype(jnp.float32)
        total = term if total is None else total + term
    return float(total) if total is not None else 0.0

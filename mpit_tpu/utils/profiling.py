"""Tracing / profiling hooks.

Reference parity (SURVEY.md §5): the reference had only ad-hoc tic/toc timers
and prints. TPU plan from the survey: ``jax.profiler`` trace hooks plus
per-step wall-clock counters — a captured trace opens in
Perfetto/TensorBoard and shows the XLA op timeline, ICI collectives
included, which is the observability the MPI version never had.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax profiler trace into ``log_dir`` (no-op when None), so
    call sites can unconditionally wrap their hot loop."""
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield


def annotate(name: str):
    """Named region on the host trace timeline (wrap a step or a phase)."""
    return jax.profiler.TraceAnnotation(name)


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


class StepTimer:
    """Wall-clock timer for jitted step loops.

    Measures *completed* work: call ``stop()`` with (or after) a
    ``block_until_ready`` on the step output, otherwise async dispatch makes
    steps look free. Keeps a skip-count so compile steps don't pollute the
    stats."""

    def __init__(self, skip_first: int = 1):
        self.skip_first = skip_first
        self._times: list[float] = []
        self._seen = 0
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, result=None) -> float:
        """Waits for ``result`` (if given) via :func:`force_completion`
        (on the v5e ``block_until_ready`` measures the same, see there),
        then records the elapsed time. Returns the step's wall seconds. A
        tuple result (e.g. a ``(state, metrics)`` step output) is spread
        so each component gets its own proof leaf."""
        if result is not None:
            if isinstance(result, tuple):
                force_completion(*result)
            else:
                force_completion(result)
        if self._t0 is None:
            raise RuntimeError("StepTimer.stop() without start()")
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self._seen += 1
        if self._seen > self.skip_first:
            self._times.append(dt)
        return dt

    @property
    def mean(self) -> float:
        return sum(self._times) / len(self._times) if self._times else float("nan")

    @property
    def count(self) -> int:
        return len(self._times)

    def summary(self) -> dict:
        if not self._times:
            return {"steps": 0}
        ts = sorted(self._times)
        return {
            "steps": len(ts),
            "mean_s": self.mean,
            "p50_s": ts[len(ts) // 2],
            "max_s": ts[-1],
        }

"""Tracing / profiling hooks.

Reference parity (SURVEY.md §5): the reference had only ad-hoc tic/toc timers
and prints. TPU plan from the survey: ``jax.profiler`` trace hooks plus
per-step wall-clock counters — a captured trace opens in
Perfetto/TensorBoard and shows the XLA op timeline, ICI collectives
included, which is the observability the MPI version never had.

``span`` is the package's one way to name host work (the fit loops, the
prefetcher, ``Batches`` and ``init_state`` use it; docs/OBSERVABILITY.md has
the names) and ``scope`` its one way to name device work; ``trace`` captures
a profile; ``unit_program_text`` is where a trace's device events get their
scope from, and ``unit_scope_table`` reads it for every instruction: the
scope path, the phase (forward, recomputation, backward, update) and where
an instruction the compiler made got them.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import re
import time
from typing import Iterator, Optional

import jax

#: durations kept a span name (the newest; count/total/max cover them all)
RING = 4096
#: what ``trace`` leaves beside the profile
UNIT_SCOPES_FILE = "unit_scopes.json"


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Capture a jax profiler trace into ``log_dir`` (no-op when None), so
    call sites can unconditionally wrap their hot loop.

    Where a fit loop ran inside it, ``<log_dir>/unit_scopes.json`` is
    written once the profiler has stopped: ``unit_scope_table()`` with the
    registered ``scopes``, the key from an ``XLA Ops`` event's instruction
    name to its scope path and phase. It costs the one fresh compile of
    ``unit_program_text``, outside the trace."""
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield
    table = unit_scope_table()
    if table is not None:
        with open(os.path.join(log_dir, UNIT_SCOPES_FILE), "w") as f:
            json.dump({"scopes": scopes(), "instructions": table}, f)


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


# every name ``scope`` was given: what tells a scope from a flax module
# (``Block_3``) or a primitive (``dot_general``) in an ``op_name``. A set of
# strings that only grows; ``reset()`` leaves it, since a program traced
# before the reset keeps its names and is not traced again.
_scopes: set[str] = set()


def scope(name: str):
    """``with scope("mlp"):`` names device work, as ``span`` names host work:
    ``jax.named_scope(name)``, with ``name`` kept in ``scopes()``. It runs
    while a program is traced and is metadata only: the arithmetic and the
    fusions are what they are without it."""
    _scopes.add(name)
    return jax.named_scope(name)


def scopes() -> list:
    """Every name ``scope`` has been given in this process, sorted."""
    return sorted(_scopes)


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
    global _unit, _unit_text, _unit_table
    _registry.clear()
    _unit = _unit_text = _unit_table = None


# (jitted program, its arguments as shapes) of the unit the newest fit loop
# dispatches: what ``unit_program_text`` compiles again
_unit = None
# its compiled text, once asked for: a second reader does not compile again
_unit_text = None
# and ``scope_table`` of that text, once asked for
_unit_table = None


def remember_unit(program, *args) -> None:
    """Called by a fit loop after its first dispatch (it takes a few ms for
    a state of hundreds of leaves, which a device at work hides), with the
    unit's program and the arguments of its next call. Only shapes, dtypes
    and shardings are kept, so no buffer outlives its donation; the program
    (and the trainer it closes over) stays referenced until the next fit
    loop or ``reset()``."""
    global _unit, _unit_text, _unit_table
    _unit_text = _unit_table = None
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


# -- the scope table: every instruction's scope path, phase and provenance ----

PHASES = ("forward", "recompute", "backward", "update", "mixed", "unnamed")
# ``transpose(jvp(loss))`` -> ``loss``: jax wraps a scope set outside a flax
# module in the transformations it passes through
_WRAPPED = re.compile(r"^(?:\w+\()*([\w.\-]+)\)*$")
_LAYER = re.compile(r"^Block_\d+$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
_ASSIGNMENT = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
# the opcodes that only move or name data: such an instruction is there for
# the one that reads it, any other for what its operands were made by
_MOVES = frozenset({
    "copy", "copy-start", "copy-done", "async-start", "async-done", "bitcast",
    "transpose", "reshape", "slice", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "pad", "broadcast", "get-tuple-element", "tuple",
    "parameter", "constant", "iota"})
# an instruction that runs other computations has its own name or none
_CONTAINERS = frozenset({"while", "conditional", "call"})


def _closing(text: str, start: int) -> int:
    """Index of the parenthesis that closes the one at ``start``."""
    depth = 0
    for at in range(start, len(text)):
        ch = text[at]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return at
    return len(text) - 1


def _instruction(rest: str):
    """``f32[8]{0} fusion(%a, %b), kind=kLoop, calls=%c, metadata={...}``
    (an instruction's text after ``=``) -> opcode, operand names, the
    computation it fuses or reduces with, ``op_name``."""
    at = _closing(rest, 0) + 1 if rest.startswith("(") else rest.find(" ")
    head, _, _ = rest[at:].partition("(")
    opcode = head.strip()
    start = at + len(head)
    end = _closing(rest, start)
    tail = rest[end + 1:]
    called = _CALLS.search(tail)
    op_name = _OP_NAME.search(tail)
    return (opcode, _OPERAND.findall(rest[start:end]),
            called.group(1) if called else None,
            op_name.group(1) if op_name else "")


def _parse(program_text: str) -> dict:
    """``{computation: {instruction: (opcode, operands, called, op_name)}}``
    in the text's order."""
    computations, current = {}, None
    for line in program_text.splitlines():
        if line.startswith("}"):
            current = None
        elif current is None:
            header = _COMPUTATION.match(line)
            if header:
                current = computations.setdefault(header.group(1), {})
        else:
            found = _ASSIGNMENT.match(line)
            if found:
                current[found.group(1)] = _instruction(found.group(2))
    return computations


def _phase_of(parts) -> Optional[str]:
    """The phase one ``op_name``'s components give, None where nothing on
    the path says: no transformation and no scope."""
    if "rematted_computation" in parts:
        return "recompute"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    if any(p.startswith("jvp(") for p in parts):
        return "forward"
    return None


def _read_op_name(op_name: str, scopes) -> tuple:
    """``(path, layer, phase)`` of one ``op_name``. Two names joined by
    ``;`` (XLA merged the instructions) are read apart: the path is that of
    the last one which holds a registered scope (where
    ``program_spans.scope_of``, scanning the whole string from its end,
    finds its scope too), the phase ``mixed`` where those that say a phase
    say different ones."""
    path, layer, phases = [], None, []
    for joined in reversed(op_name.split(";")):
        parts = joined.split("/")
        names = [m.group(1) for m in map(_WRAPPED.match, parts) if m]
        found = [n for n in names if n in scopes]
        phase = _phase_of(parts) or ("update" if found else None)
        if phase and phase not in phases:
            phases.append(phase)
        if found and not path:
            path = found
            layer = next((n for n in names if _LAYER.match(n)), None)
    phase = None if not phases else phases[0] if len(phases) == 1 else "mixed"
    return path, layer, phase


def _agreed(rows) -> Optional[dict]:
    """What instructions that agree on their innermost scope hand on: the
    first one's path, the layer and the phase they share (else no layer,
    ``mixed``); None where there is none or they disagree."""
    rows = [r for r in rows if r["path"]]
    if not rows or len({r["path"][-1] for r in rows}) != 1:
        return None
    layers = {r["layer"] for r in rows}
    phases = {r["phase"] for r in rows}
    return {"path": list(rows[0]["path"]),
            "layer": layers.pop() if len(layers) == 1 else None,
            "phase": phases.pop() if len(phases) == 1 else "mixed"}


def scope_table(program_text: str, scopes) -> dict:
    """``{instruction: row}`` for every instruction of every computation of
    a compiled program's text, keyed as a trace names its event
    (``fusion.158``, ``copy.12``, ``delta_bwd.3``). ``scopes`` are the
    names that count as scopes (``scopes()``); any other component of an
    ``op_name`` is a flax module, a transformation or a primitive. A row:

    ``path``: the scope names on the instruction's ``op_name``, outermost
    first, unwrapped from the transformations jax names them through
    (``transpose(jvp(loss))`` is ``loss``). ``layer``: its ``Block_<i>``
    component, or None. ``opcode``: the HLO opcode.

    ``phase``: ``recompute`` where the ``op_name`` holds
    ``rematted_computation``; else ``backward`` where a component is wrapped
    in ``transpose(``; else ``forward`` where one is wrapped in ``jvp(``;
    else ``update`` (a scope with no differentiation on its path:
    ``optimizer``, ``elastic``, ``grad_exchange``); ``mixed`` where two
    names joined by ``;``, or the instructions a row inherits from, say
    different ones; ``unnamed`` where nothing says.

    ``how``: where path and phase come from. ONE rule, in this order:
    ``own``: the instruction's ``op_name`` holds a registered scope.
    ``fused``: a fusion without one, whose fused instructions' own scopes
    agree on the innermost. ``user``: the named instructions of its
    computation that read its result agree on the innermost scope (the
    consumer asked for the copy or the layout). ``operand``: those that
    made its operands do. ``none``: nothing found; the path is empty.
    Inheritance is chained (a ``copy-start`` gets its scope from its
    ``copy-done``, which got it from the fusion that reads it) and runs in
    rounds, each seeing what the rounds before it named, until nothing
    changes: first every instruction asks on its preferred side alone,
    then on both. The preferred side of an instruction that only moves
    data (``_MOVES``, or a fusion of nothing else) is its users; of one
    that computes (the compiler's grouped products, ``ragged-dot-none.N``,
    whose result the dispatch's scatter reads) its operands. A scope
    flows one way through an instruction that only moves data: a
    ``tuple`` named by some of its operands does not name the others. A
    ``while``, ``conditional`` or ``call`` inherits nothing. An
    ``op_name`` that holds no scope still gives its phase if it says one.
    The instructions of a fused computation or a reducer, which no trace
    event names, are ``own`` or ``none``.

    ``holds``: for a fusion, ``[scope, phase]`` of its fused instructions'
    innermost scopes other than its own (``[["optimizer", "update"]]`` on
    a weight gradient's fusion that XLA gave the weight's update too).
    ``fused``: for a fusion, the opcodes of its fused computation."""
    scopes = frozenset(scopes)
    computations = _parse(program_text)
    table = {}
    for instructions in computations.values():
        for name, (opcode, _, _, op_name) in instructions.items():
            path, layer, phase = _read_op_name(op_name, scopes)
            table[name] = {"path": path, "layer": layer, "phase": phase,
                           "opcode": opcode, "how": "own" if path else None}
    inside = set()  # computations that run as part of one instruction
    for instructions in computations.values():
        for name, (opcode, _, called, _) in instructions.items():
            if called not in computations or opcode == "call":
                continue
            inside.add(called)
            if opcode != "fusion":
                continue
            row, fused = table[name], [table[n] for n in computations[called]]
            row["fused"] = sorted({r["opcode"] for r in fused})
            agreed = None if row["how"] else _agreed(fused)
            if agreed:
                row.update(agreed, how="fused",
                           phase=row["phase"] or agreed["phase"])
            own = row["path"][-1] if row["path"] else None
            row["holds"] = [list(held) for held in sorted(
                {(r["path"][-1], r["phase"]) for r in fused
                 if r["path"] and r["path"][-1] != own})]
    edges = {"user": {}, "operand": {}}  # within a computation, by name
    moves, asks = {}, []  # it only moves data; it has no scope yet
    for computation, instructions in computations.items():
        if computation in inside:
            continue
        for name, (opcode, operands, _, _) in instructions.items():
            makers = [o for o in operands if o in instructions]
            edges["operand"][name] = makers
            for maker in makers:
                edges["user"].setdefault(maker, []).append(name)
            fused = table[name].get("fused")
            moves[name] = opcode in _MOVES or (
                fused is not None and set(fused) <= _MOVES)
            if not table[name]["how"] and opcode not in _CONTAINERS:
                asks.append(name)

    def named(name, side):
        """The rows on one side of ``name`` that hand a scope on to it."""
        return [table[n] for n in edges[side].get(name, ())
                if table[n]["how"] in ("own", "fused", side)
                or table[n]["how"] and not moves[n]]

    for both in (False, True):
        found = True
        while found:
            found = {}
            for name in asks:
                first, other = (("user", "operand") if moves[name]
                                else ("operand", "user"))
                for side in (first, other) if both else (first,):
                    agreed = _agreed(named(name, side))
                    if agreed:
                        found[name] = dict(agreed, how=side)
                        break
            asks = [name for name in asks if name not in found]
            for name, agreed in found.items():
                table[name].update(
                    agreed, phase=table[name]["phase"] or agreed["phase"])
    for row in table.values():
        row["how"] = row["how"] or "none"
        row["phase"] = row["phase"] or "unnamed"
    return table


def unit_scope_table() -> Optional[dict]:
    """``scope_table`` of ``unit_program_text()`` with the registered
    ``scopes()``, or None where that is None. Kept: it costs no compile
    beyond the text's one."""
    global _unit_table
    if _unit_table is None:
        text = unit_program_text()
        if text is None:
            return None
        _unit_table = scope_table(text, _scopes)
    return _unit_table


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

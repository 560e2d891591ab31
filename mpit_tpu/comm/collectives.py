"""Collectives: the TPU-native ``mpiT.Allreduce / Bcast / Barrier``.

Reference parity (SURVEY.md §2 comp. 1, BASELINE.json:5): mpiT exposed MPI
collectives over flat Torch storages. Here the collectives are XLA
collectives over a mesh axis — they must be called *inside* an SPMD context
(``jax.shard_map`` / ``jit`` over a Mesh) where the worker axis name is bound,
and they lower to ICI all-reduces rather than host-mediated MPI. All
functions are pytree-aware: a whole parameter pytree all-reduces in one call,
matching the reference's flat-tensor usage without requiring flattening.

Host-level process synchronization (``mpiT.Barrier`` outside compute) maps to
``multihost_utils.sync_global_devices``.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

# `from mpit_tpu.comm.topology import ...` (by full module path) rather than
# an attribute import: the package re-exports a `topology()` *function* that
# shadows the submodule attribute of the same name.
from mpit_tpu.comm.topology import topology as _current_topology
from mpit_tpu import quant as _quant

# Reduction ops, mirroring mpiT.SUM/PROD/MAX/MIN constants (SURVEY.md §2 L2
# row). AVG is a convenience the reference implemented as SUM + divide
# (SURVEY.md §3(d): "grad /= size").
SUM = "sum"
PROD = "prod"
MAX = "max"
MIN = "min"
AVG = "avg"


def _pprod(x, axis_name):
    """Product reduction. XLA has no product collective, so this is
    ``all_gather`` + ``prod`` — exact for any sign, but O(W) peak memory
    per leaf; avoid PROD on large leaves."""
    return jnp.prod(lax.all_gather(x, axis_name), axis=0)


# every exported op constant dispatches here (AVG is pmean, handled in
# allreduce directly) — the table and the constants must agree
_REDUCERS = {
    SUM: lax.psum,
    PROD: _pprod,
    MAX: lax.pmax,
    MIN: lax.pmin,
}


def _axis(axis_name: Optional[str]) -> str:
    return axis_name if axis_name is not None else _current_topology().worker_axis


def psum(tree: Any, axis_name: Optional[str] = None) -> Any:
    return lax.psum(tree, _axis(axis_name))


def pmean(tree: Any, axis_name: Optional[str] = None) -> Any:
    return lax.pmean(tree, _axis(axis_name))


def pmax(tree: Any, axis_name: Optional[str] = None) -> Any:
    return lax.pmax(tree, _axis(axis_name))


def pmin(tree: Any, axis_name: Optional[str] = None) -> Any:
    return lax.pmin(tree, _axis(axis_name))


def allreduce(
    tree: Any,
    op: str = SUM,
    axis_name: Optional[str] = None,
    quant: Optional[str] = None,
) -> Any:
    """``mpiT.Allreduce``: reduce a pytree across the worker axis, all get it.

    ``op=PROD`` dispatches to the ``all_gather`` + ``prod`` reducer (XLA
    has no product collective) — exact for any sign, but O(W) peak memory
    per leaf; avoid PROD on large leaves.

    ``quant="bf16"|"int8"`` runs the EQuARX-style quantized scheme
    (:func:`quantized_allreduce`) instead of the raw collective — SUM/AVG
    only, and LOSSY per call: the quantization error is bounded (one
    rounding step per hop) but not fed back at this level. Callers that
    reduce the same stream repeatedly (gradient exchange) should hold an
    error-feedback residual and call :func:`quantized_allreduce`
    directly.
    """
    axis = _axis(axis_name)
    if quant not in (None, "off"):
        if op not in (SUM, AVG):
            raise ValueError(
                f"quantized allreduce supports SUM/AVG, not {op!r}"
            )
        reduced, _, _ = quantized_allreduce(
            tree, axis_name=axis, mode=quant, mean=(op == AVG)
        )
        return reduced
    if op == AVG:
        return lax.pmean(tree, axis)
    try:
        reducer = _REDUCERS[op]
    except KeyError:
        raise ValueError(f"unknown reduction op: {op!r}") from None
    return jax.tree.map(functools.partial(reducer, axis_name=axis), tree)


def _quant_allreduce_leaf(x, axis: str, mode: str, mean: bool, r2=None):
    """One leaf of the quantized allreduce: the bandwidth-optimal
    reduce-scatter + all-gather decomposition with quantized codes on
    both wire hops (EQuARX, PAPERS.md arXiv:2506.17615).

    Per worker: pad the flat leaf to W·chunk, view it as W destination
    rows, quantize each row against its own absmax block scale, and
    ``all_to_all`` the codes — worker k receives every worker's row k,
    dequantizes, and sums in f32 (the accumulate stays full precision;
    only the wire legs are narrow). The reduced chunk is re-quantized
    once and ``all_gather``-ed back.

    Returns ``(reduced, sent_deq, new_r2)``:

    - ``sent_deq`` is THIS worker's dequantized first-hop contribution —
      what the receivers actually summed — so a caller can form the
      level-1 error-feedback residual ``x - sent_deq`` without a second
      quantization pass;
    - ``r2``/``new_r2`` is the level-2 residual on the OWNED reduced
      chunk (shape ``(ceil(n/W),)``): the second hop's rounding,
      compensated into the next round's re-quantization. Chunk ownership
      is stable across calls, so the feedback lands on the same stream.
    """
    w = lax.axis_size(axis)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = -n % w
    if pad:
        flat = jnp.pad(flat, (0, pad))
    rows = flat.reshape(w, -1)

    codes, scales = _quant.quantize_rows_jnp(rows, mode)
    sent_deq = _quant.dequantize_rows_jnp(codes, scales, mode)
    # first wire hop: row j of every worker travels to worker j
    codes_x = lax.all_to_all(codes, axis, split_axis=0, concat_axis=0)
    if mode == "int8":
        scales_x = lax.all_to_all(scales, axis, split_axis=0, concat_axis=0)
    else:
        scales_x = scales  # bf16 is scale-free; nothing to move
    contrib = _quant.dequantize_rows_jnp(codes_x, scales_x, mode)
    red = jnp.sum(contrib, axis=0)
    if mean:
        red = red / w
    if r2 is not None:
        red = red + jnp.asarray(r2, jnp.float32)

    # second wire hop: one re-quantization of the reduced chunk, gathered
    rcodes, rscale = _quant.quantize_jnp(red, mode)
    new_r2 = red - _quant.dequantize_jnp(rcodes, rscale, mode)
    g_codes = lax.all_gather(rcodes, axis)
    if mode == "int8":
        g_scales = lax.all_gather(rscale, axis).reshape(w, 1)
    else:
        g_scales = None
    out = _quant.dequantize_rows_jnp(g_codes, g_scales, mode).reshape(-1)

    out = out[:n].reshape(shape).astype(dtype)
    sent_deq = sent_deq.reshape(-1)[:n].reshape(shape)
    return out, sent_deq, new_r2


def quantized_allreduce(
    tree: Any,
    axis_name: Optional[str] = None,
    mode: str = "int8",
    mean: bool = False,
    residual: Any = None,
    residual2: Any = None,
) -> tuple:
    """Quantized SUM (or mean) allreduce with two-level error feedback.

    Returns ``(reduced_tree, new_residual_tree, new_residual2_tree)``.
    ``residual`` (same structure as ``tree``, f32 leaves) compensates
    each worker's CONTRIBUTION before the first-hop quantization —
    ``c = x + residual``, new residual ``c - deq(quant(c))`` — the
    standard EF recurrence that keeps the accumulated reduction unbiased
    across repeated calls on one stream (docs/WIRE.md). ``residual2``
    (leaves shaped ``(ceil(leaf_size/W),)``) compensates the second
    hop's re-quantization of this worker's OWNED reduced chunk the same
    way. Pass both back in on the next call; with ``None`` the new
    residuals are still returned (what one call lost), so a caller can
    start the loop without building zero trees."""
    if mode not in ("bf16", "int8"):
        raise ValueError(
            f"quantized allreduce mode {mode!r}: expected 'bf16' or 'int8'"
        )
    axis = _axis(axis_name)
    leaves, treedef = jax.tree.flatten(tree)
    res_leaves = (
        jax.tree.flatten(residual)[0]
        if residual is not None
        else [None] * len(leaves)
    )
    res2_leaves = (
        jax.tree.flatten(residual2)[0]
        if residual2 is not None
        else [None] * len(leaves)
    )
    out, new_res, new_res2 = [], [], []
    for x, r, r2 in zip(leaves, res_leaves, res2_leaves):
        c = jnp.asarray(x, jnp.float32)
        if r is not None:
            c = c + jnp.asarray(r, jnp.float32)
        reduced, sent, nr2 = _quant_allreduce_leaf(c, axis, mode, mean, r2)
        out.append(reduced.astype(jnp.asarray(x).dtype))
        new_res.append(c - sent)
        new_res2.append(nr2)
    return (
        jax.tree.unflatten(treedef, out),
        jax.tree.unflatten(treedef, new_res),
        jax.tree.unflatten(treedef, new_res2),
    )


def quantized_psum_scatter(
    flat: Any, axis_name: Optional[str] = None, mode: str = "int8"
) -> Any:
    """Quantized ``lax.psum_scatter(..., tiled=True)``: the first hop of
    :func:`quantized_allreduce` alone — each worker keeps the f32 sum of
    everyone's quantized chunk k. The ZeRO gradient-scatter hook
    (``parallel/zero.py``): the wire moves 1- or 2-byte codes instead of
    f32, the accumulate stays full precision. STATELESS — no error
    feedback at this level (the rounding is one bounded step per call;
    the dynamics plane is the convergence guardrail)."""
    if mode in (None, "off"):
        return lax.psum_scatter(flat, _axis(axis_name), tiled=True)
    if mode not in ("bf16", "int8"):
        raise ValueError(
            f"quantized psum_scatter mode {mode!r}: "
            "expected 'bf16' or 'int8'"
        )
    axis = _axis(axis_name)
    w = lax.axis_size(axis)
    x = jnp.asarray(flat, jnp.float32)
    rows = x.reshape(w, -1)  # requires W-divisible flats, like tiled=True
    # The ZeRO scatter is stateless by design: each shard owner sees
    # fresh gradients every step, and the dynamics plane is the
    # convergence guardrail (docstring above).
    # mpit-analysis: ef-off[ZeRO scatter is stateless by design]
    codes, scales = _quant.quantize_rows_jnp(rows, mode)
    codes_x = lax.all_to_all(codes, axis, split_axis=0, concat_axis=0)
    if mode == "int8":
        scales = lax.all_to_all(scales, axis, split_axis=0, concat_axis=0)
    contrib = _quant.dequantize_rows_jnp(codes_x, scales, mode)
    return jnp.sum(contrib, axis=0)


def allgather(
    tree: Any, axis_name: Optional[str] = None, tiled: bool = False
) -> Any:
    """All-gather each leaf across the worker axis (new leading worker dim,
    or concatenated along axis 0 when ``tiled``)."""
    axis = _axis(axis_name)
    return jax.tree.map(
        lambda x: lax.all_gather(x, axis, tiled=tiled), tree
    )


def bcast(tree: Any, root: int = 0, axis_name: Optional[str] = None) -> Any:
    """``mpiT.Bcast``: every worker receives root's value.

    Implemented as a masked psum — one collective, no gather of W copies:
    ``psum(where(rank == root, x, 0))``. Exact for floats (no reduction
    reordering across distinct values: all non-root contributions are 0).
    """
    axis = _axis(axis_name)
    idx = lax.axis_index(axis)
    world = lax.axis_size(axis)  # static inside shard_map
    if isinstance(root, int) and not 0 <= root < world:
        raise ValueError(
            f"bcast root={root} out of range for worker axis of size {world}"
        )

    def _pick(x):
        x = jnp.asarray(x)
        zero = jnp.zeros_like(x)
        contrib = jnp.where(idx == root, x, zero)
        return lax.psum(contrib, axis)

    return jax.tree.map(_pick, tree)


def device_barrier(axis_name: Optional[str] = None):
    """In-SPMD barrier: a psum of 1 forces a rendezvous on the worker axis.

    SPMD programs are lockstep by construction, so this is rarely needed;
    it exists for ``mpiT.Barrier`` parity inside compiled steps and returns
    the world size (a free ``Comm_size`` check).
    """
    return lax.psum(jnp.ones((), jnp.int32), _axis(axis_name))


def barrier(name: str = "mpit_barrier") -> None:
    """Host-level barrier across processes (``mpiT.Barrier`` outside jit).

    On a single process this is a no-op. Multi-host it blocks until every
    process reaches the same named point.
    """
    if _current_topology().process_count > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def reduce_scatter(
    tree: Any,
    axis_name: Optional[str] = None,
    scatter_dimension: int = 0,
    tiled: bool = True,
) -> Any:
    """Reduce-scatter: sum across workers, each worker keeps its 1/W shard
    (``lax.psum_scatter``). The building block of bandwidth-optimal
    allreduce (reduce_scatter + all_gather) and of sharded-optimizer
    (ZeRO-style) updates; leaves must be divisible by W along
    ``scatter_dimension``."""
    axis = _axis(axis_name)
    return jax.tree.map(
        lambda x: lax.psum_scatter(
            x, axis, scatter_dimension=scatter_dimension, tiled=tiled
        ),
        tree,
    )


def ppermute_ring(
    tree: Any, shift: int = 1, axis_name: Optional[str] = None
) -> Any:
    """Ring neighbor-exchange: each worker sends to ``(rank+shift) % W``.

    The closest XLA analogue to point-to-point Send/Recv (SURVEY.md §7 "hard
    parts": no tagged p2p on TPU). Used by ring-style algorithms; the PS
    protocol instead uses ``mpit_tpu.transport``.
    """
    axis = _axis(axis_name)
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.tree.map(lambda x: lax.ppermute(x, axis, perm), tree)

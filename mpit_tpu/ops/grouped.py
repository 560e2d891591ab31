"""Grouped matrix products over a buffer of rows sorted by group, as Pallas
kernels for the TPU: the held experts' products (``ops/moe._expert_rows``).

``gmm(lhs, rhs, sizes, tiles)``: ``lhs`` ``(m, k)`` holds the rows of group
0, then those of group 1, ..., ``sizes`` ``(G,)`` int32 how many each has
(from row 0; ``sum(sizes) <= m``), ``rhs`` ``(G, k, n)``. Row ``i`` of
group ``g`` comes out as ``lhs[i] @ rhs[g]``, accumulated in float32 and
rounded once to ``lhs``' dtype, which is what ``lax.ragged_dot(lhs, rhs,
sizes, preferred_element_type=float32).astype(lhs.dtype)`` gives on the
groups' rows; every row past the last group comes out 0. The compiler's
kernel behind ``ragged_dot`` leaves those rows as they were in memory, so
its callers mask every operand and result (``moe._expert_rows``); here the
kernels write the zeros themselves, forward and backward:

- forward and the rows' cotangent (``ct @ rhs[g]^T``): one grid step a
  row tile of ``tm`` rows and a group that has rows in it, so a tile that
  two groups share is computed once for each (megablox's plan); each step
  keeps its group's rows and zeroes, on a tile's first visit, the rows
  that belong to no group. The tiles wholly past the last group are
  neither loaded nor computed: one step each writes their zeros;
- the weights' cotangent (``lhs[rows of g]^T @ ct[rows of g]`` a group,
  ``(G, k, n)`` in ``rhs``' dtype): the same visits, one more for each
  group without rows (its block is zeroed), and the rows of a tile that are
  not its group's selected away before the product, so that a NaN in the
  buffer's tail reaches nothing.

Written after megablox's ``gmm`` and ``tgmm``
(``jax.experimental.pallas.ops.tpu.megablox``), which leave the rows past
the groups unwritten and the products in a separate precision argument.

**What runs where.** ``takes`` decides, from what the caller can observe:
the kernels where the backend is a TPU and the shape tiles (``k`` and ``n``
whole multiples of 128, the buffer a whole number of row tiles), the
compiler's ``ragged_dot`` everywhere else. ``choose_tiles`` takes the tiles
from the rows a group holds under uniform routing and the two widths, as
``flash_attention.choose_blocks`` does for the flash kernels. In a trace the
products run under the scopes ``moe_gmm`` (forward and the rows' cotangent)
and ``moe_tgmm`` (the weights' cotangent), so each custom call is named
``moe_gmm.N`` / ``moe_tgmm.N`` and stands under its caller's scope.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ``pallas_supported`` is read from here (``moe_ffn_held``), so that one
# patch forces the choice (``scripts/lowered_step.py``, the tests)
from mpit_tpu.ops.elastic import pallas_interpret, pallas_supported
from mpit_tpu.utils import profiling

_LANE = 128
#: what a kernel may take of VMEM (the chip's compiler is told so), and
#: the part of it the tile chooser's estimate has to stay under
_VMEM_LIMIT = 48 * 1024 * 1024
_VMEM_BUDGET = _VMEM_LIMIT * 2 // 3
#: the row tiles the chooser weighs, and the largest output tile side
_ROW_TILES = (128, 256, 512)
_TILE_CAP = 1024
#: a grid step's fixed cost, in rows of a tile's work: a group of ``r``
#: rows at a random offset touches ``(r + tm - 1) / tm`` tiles, each
#: costing ``tm + _STEP_ROWS`` rows' time
_STEP_ROWS = 96

__all__ = ["choose_tiles", "gmm", "rows_tiled", "takes"]


def _divisors(size: int, cap: int):
    """Multiples of 128 up to ``cap`` that divide ``size``, largest first."""
    return [t for t in range(min(size, cap) // _LANE * _LANE, 0, -_LANE)
            if size % t == 0]


def vmem_estimate(kernel: str, tm: int, tk: int, tn: int,
                  itemsize: int) -> int:
    """An upper estimate of the VMEM ``kernel`` ("gmm" or "tgmm") holds:
    its operand and output tiles double-buffered and its float32
    temporaries: in "gmm" three of the output tile's size (the product,
    what the tile kept, their select), in "tgmm" the accumulator, the
    product and the operands selected in float32."""
    if kernel == "gmm":  # (tm, tk) @ (tk, tn) -> (tm, tn)
        tiles = 2 * (tm * tk + tk * tn + tm * tn) * itemsize
        return tiles + 3 * tm * tn * 4
    tiles = 2 * (tm * tk + tm * tn + tk * tn) * itemsize
    return tiles + 2 * tk * tn * 4 + (tm * tk + tm * tn) * 4


def _row_tile(rows_per_group: int) -> int:
    """The row tile that costs least a group: ``(r + tm - 1) / tm`` tiles
    touched, ``tm + _STEP_ROWS`` each."""
    return min(_ROW_TILES, key=lambda tm: (
        (rows_per_group + tm - 1) / tm * (tm + _STEP_ROWS), tm))


def choose_tiles(rows_per_group: int, k: int, n: int, dtype):
    """``(tm, tk, tn)`` for the forward product ``(m, k) @ (k, n)``, the rows'
    cotangent ``(m, n) @ (n, k)`` and the weights' cotangent ``(k, n)`` a
    group, in that order, for groups of about ``rows_per_group`` rows; None
    where a row product's whole contraction does not fit.

    ``tm`` weighs padding against steps (``_row_tile``). The row products
    take the whole contraction in one block (``tk`` = ``k``, ``n``), so
    that a group's weights stay in VMEM across its tiles and each step
    loads one row tile, and the widest output tile up to 1,024 that divides
    their width; the weights' cotangent takes the largest output block.
    Every tile keeps ``vmem_estimate`` under the budget. ``k`` and ``n``
    are whole multiples of 128 (``takes``)."""
    itemsize = jnp.dtype(dtype).itemsize
    tm = _row_tile(rows_per_group)

    def rows_product(kc, width):
        return next(((tm, kc, tn) for tn in _divisors(width, _TILE_CAP)
                     if vmem_estimate("gmm", tm, kc, tn, itemsize)
                     <= _VMEM_BUDGET), None)

    fits = [(tk, tn) for tk in _divisors(k, _TILE_CAP)
            for tn in _divisors(n, _TILE_CAP)
            if vmem_estimate("tgmm", tm, tk, tn, itemsize) <= _VMEM_BUDGET]
    forward, rows_ct = rows_product(k, n), rows_product(n, k)
    if not (forward and rows_ct and fits):
        return None
    return forward, rows_ct, (tm, *max(fits, key=lambda t: (t[0] * t[1], t[1])))


def takes(on_tpu: bool, rows: int, k: int, n: int,
          rows_per_group: int) -> bool:
    """Whether the held experts' products run as these kernels: on a TPU,
    with both widths whole multiples of 128, the buffer's ``rows`` a whole
    number of row tiles and tiles that fit (``choose_tiles``). A pure
    function of what the caller sees."""
    return (on_tpu and not k % _LANE and not n % _LANE
            and not rows % _row_tile(rows_per_group)
            and choose_tiles(rows_per_group, k, n, jnp.bfloat16) is not None)


def _plan(sizes, m: int, tm: int, every_group: bool):
    """The grid's visits to row tiles, in order: for each group with rows,
    each tile its rows touch (and, ``every_group``, one visit to a group
    without rows, on the tile before its place). Returns ``(group, tile,
    starts, ends, live)``: per visit its group and row tile (arrays of the
    largest count, ``m / tm + G``; past ``live`` the last visit's), each
    group's first and past-the-last row, and the visits' count ``(1,)``."""
    ends = jnp.cumsum(sizes).astype(jnp.int32)
    starts = ends - sizes
    has = sizes > 0
    first = jnp.where(has, starts // tm, jnp.maximum(starts - 1, 0) // tm)
    count = jnp.where(has, (ends + tm - 1) // tm - starts // tm,
                      1 if every_group else 0)
    through = jnp.cumsum(count)
    live = through[-1]
    visit = jnp.arange(m // tm + sizes.shape[0], dtype=jnp.int32)
    last = jnp.maximum(jnp.minimum(visit, live - 1), 0)
    group = jnp.minimum(jnp.searchsorted(through, last, side="right"),
                        sizes.shape[0] - 1).astype(jnp.int32)
    tile = first[group] + last - (through[group] - count[group])
    tile = jnp.minimum(tile, m // tm - 1).astype(jnp.int32)
    return group, tile, starts, ends, live.astype(jnp.int32)[None]


def _rows_kernel(group, tile, starts, ends, live, out_tile,
                 lhs_ref, rhs_ref, out_ref, *, tm, contract):
    v = pl.program_id(1)
    is_live = v < live[0]
    g = group[v]
    rows = out_tile[v] * tm + lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    mine = (rows >= starts[g]) & (rows < ends[g]) & is_live
    # a tile's first visit zeroes the rows no group holds; a later one (the
    # next group's) keeps what the earlier stored
    first = (v == 0) | (out_tile[jnp.maximum(v - 1, 0)] != out_tile[v])
    kept = jnp.where(first, 0.0, out_ref[...].astype(jnp.float32))
    product = lax.cond(
        is_live,
        lambda: lax.dot_general(lhs_ref[...], rhs_ref[...],
                                (((1,), (contract,)), ((), ())),
                                preferred_element_type=jnp.float32),
        lambda: jnp.zeros(out_ref.shape, jnp.float32))
    out_ref[...] = jnp.where(mine, product, kept).astype(out_ref.dtype)


# jitted, so that a call's name is its scope's (``moe_gmm.N``) and not
# the transforms' round it (``transpose_jvp_moe_gmm__.N``)
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _rows_call(lhs, rhs, sizes, tiles, transpose, interpret):
    """``lhs @ rhs[g]`` (``transpose``: ``rhs[g]^T``) for each group's rows,
    0 past the last group, in ``lhs``' dtype; the contraction whole."""
    tm, _, tn = tiles
    m, kc = lhs.shape
    n = rhs.shape[1] if transpose else rhs.shape[2]
    tiles_m = m // tm
    group, tile, starts, ends, live = _plan(sizes, m, tm, every_group=False)
    # past the live visits come the tiles past the last group, one visit
    # each, loading what the last live visit loaded (so nothing)
    visit = jnp.arange(tile.shape[0], dtype=jnp.int32)
    beyond = -(-ends[-1] // tm)
    out_tile = jnp.where(visit < live[0], tile,
                         jnp.minimum(beyond + visit - live[0], tiles_m - 1))

    def lhs_map(j, v, group, tile, starts, ends, live, out_tile):
        return tile[v], 0

    def rhs_map(j, v, group, tile, starts, ends, live, out_tile):
        return (group[v], j, 0) if transpose else (group[v], 0, j)

    def out_map(j, v, group, tile, starts, ends, live, out_tile):
        return out_tile[v], j

    # the rhs block's group axis is squeezed: (kc, tn), or (tn, kc)
    with profiling.scope("moe_gmm"):
        return pl.pallas_call(
            functools.partial(_rows_kernel, tm=tm,
                              contract=1 if transpose else 0),
            out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=6,
                grid=(n // tn, live[0] + tiles_m - beyond),
                in_specs=[pl.BlockSpec((tm, kc), lhs_map),
                          pl.BlockSpec((None, tn, kc) if transpose
                                       else (None, kc, tn), rhs_map)],
                out_specs=pl.BlockSpec((tm, tn), out_map),
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(group, tile, starts, ends, live, out_tile, lhs, rhs)


def _weights_kernel(group, tile, starts, ends, live,
                    lhs_ref, rhs_ref, out_ref, acc_ref, *, tm):
    v = pl.program_id(2)
    last = pl.num_programs(2) - 1
    g = group[v]

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ends[g] > starts[g])
    def _():
        def mine(ref):
            rows = tile[v] * tm + lax.broadcasted_iota(
                jnp.int32, ref.shape, 0)
            keep = (rows >= starts[g]) & (rows < ends[g])
            # selected, not multiplied: a NaN in another row stays there
            return jnp.where(keep, ref[...].astype(jnp.float32),
                             0.0).astype(ref.dtype)

        acc_ref[...] += lax.dot_general(
            mine(lhs_ref), mine(rhs_ref), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((v == last) | (group[jnp.minimum(v + 1, last)] != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _weights_call(lhs, ct, sizes, tiles, dtype, interpret):
    """``lhs[rows of g]^T @ ct[rows of g]`` for each group, ``(G, k, n)`` in
    ``dtype``; a group without rows gets zeros."""
    tm, tk, tn = tiles
    m, k = lhs.shape
    n = ct.shape[1]
    groups = sizes.shape[0]
    group, tile, starts, ends, live = _plan(sizes, m, tm, every_group=True)

    def lhs_map(j, i, v, group, tile, starts, ends, live):
        return tile[v], i

    def ct_map(j, i, v, group, tile, starts, ends, live):
        return tile[v], j

    def out_map(j, i, v, group, tile, starts, ends, live):
        return group[v], i, j

    with profiling.scope("moe_tgmm"):
        return pl.pallas_call(
            functools.partial(_weights_kernel, tm=tm),
            out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(n // tn, k // tk, live[0]),
                in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                          pl.BlockSpec((tm, tn), ct_map)],
                out_specs=pl.BlockSpec((None, tk, tn), out_map),
                scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(group, tile, starts, ends, live, lhs, ct)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, sizes, tiles, interpret):
    return _rows_call(lhs, rhs, sizes, tiles[0], False, interpret)


def _gmm_fwd(lhs, rhs, sizes, tiles, interpret):
    return _gmm(lhs, rhs, sizes, tiles, interpret), (lhs, rhs, sizes)


def _gmm_bwd(tiles, interpret, residuals, ct):
    lhs, rhs, sizes = residuals
    d_lhs = _rows_call(ct, rhs, sizes, tiles[1], True, interpret)
    d_rhs = _weights_call(lhs, ct, sizes, tiles[2], rhs.dtype, interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs, rhs, sizes, tiles):
    """The grouped product (module docstring) under one ``custom_vjp``,
    compiled on a TPU and interpreted on the CPU. ``tiles``:
    ``choose_tiles``' three: ``m`` a whole number of ``tm``, the row
    products' contractions whole, the widths whole numbers of their
    tiles."""
    m, k = lhs.shape
    n = rhs.shape[2]
    (tm, tk, tn), (_, tk_t, tn_t), (_, tk_w, tn_w) = tiles
    if m % tm or tk != k or n % tn or tk_t != n or k % tn_t or k % tk_w \
            or n % tn_w:
        raise ValueError(f"gmm: tiles {tiles} do not tile ({m}, {k}) @ "
                         f"({k}, {n})")
    return _gmm(lhs, rhs, sizes.astype(jnp.int32), tuple(tiles),
                pallas_interpret())


def rows_tiled(sizes, tm: int):
    """The rows ``gmm`` computes in one pass over groups of ``sizes``: the
    row tiles its groups touch times ``tm``, a tile that two groups share
    counted once for each."""
    ends = jnp.cumsum(sizes)
    count = (ends + tm - 1) // tm - (ends - sizes) // tm
    return jnp.sum(jnp.where(sizes > 0, count, 0)) * tm

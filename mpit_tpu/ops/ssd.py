"""The Mamba-2 recurrence over a sequence, in chunks (state-space duality).

For each head ``h`` with a state ``S`` in ``R^{P x N}``, ``S_0 = 0``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

``A`` is a negative scalar a head, ``dt`` a positive scalar a head and step,
``B_t``, ``C_t`` in ``R^N`` are shared by the ``H / G`` heads of a group. Taken
step by step this is ``T`` dependent updates of a ``P x N`` state; the chunked
form (Dao and Gu, "Transformers are SSMs", 2024, section 6) makes it matrix
products. With ``a_t = dt_t A`` and ``cum_i`` the sum of ``a`` over a chunk's
steps up to and including ``i``:

- inside a chunk, ``y_i = sum_{j <= i} L_ij (C_i . B_j) dt_j x_j`` with
  ``L_ij = exp(cum_i - cum_j)``: a masked ``Q x Q`` product a head and chunk;
- a chunk's closing state from nothing, ``sum_j exp(cum_last - cum_j)
  dt_j x_j B_j^T``;
- the states carried chunk to chunk, ``S_in' = exp(cum_last) S_in + closing``;
- ``C_i`` times the carried state, decayed to step ``i``: ``exp(cum_i) S_in
  C_i``, added in.

Every decay is the exponential of a difference that is never positive
(``cum_i - cum_j`` for ``j <= i``), so nothing overflows whatever the chunk's
total decay; ``log_decay_min``, the most negative ``cum_last`` met, says
whether a form factorised as ``exp(cum_i) · exp(-cum_j)`` would (below about
-88 in float32). Decays and their sums are float32; the products take their
operands in ``x``'s dtype and accumulate in float32.

**What runs where.** ``ssd`` chooses for itself, from what it can observe, as
``flash_attention`` does. Where the backend is a TPU and the shape tiles
(``tiles``: the chunk, the state ``N`` and a group's channels ``H / G · P`` are
whole multiples of 128, and ``P`` divides 128 or is a multiple of it) it runs
two Pallas kernels under one ``jax.custom_vjp``, in a trace ``ssd_fwd`` and
``ssd_bwd``: a grid of (batch, group, chunk), the chunks in order (backward in
reverse), the group's ``P x N`` states carried from chunk to chunk in a float32
VMEM scratch, a chunk's ``Q x Q`` decays built from ``cum``, used and dropped
in VMEM, ``C B^T`` taken once a group, ``x dt`` and ``D x`` inside, so that
``y`` is rounded once. The forward also writes each chunk's entering state,
the backward's residual; the backward carries ``dS``, rebuilds the same
masked decays and returns the cotangents of ``x``, ``dt``, ``cum``, ``B``,
``C`` (summed over the group's heads in the step) and ``D``. Everywhere else
(the CPU, a chunk of 8, a head of 8 channels, ``carry_state=False``) it runs
the chunked form above in ``jax.numpy``, differentiated by jax, which is the
specification the kernels are tested against (``tests/test_ssd_kernel.py``,
interpret mode). Round the kernels, in ``jax.numpy`` and differentiated by
jax: the padding of a short last chunk, ``cum = cumsum(dt A)``, ``D`` spread
over its head's channels, ``log_decay_min``. The kernels keep the rule above
(every exponent a masked difference, backward too) and the precision: decays,
their sums and the state float32, the products' operands ``x``'s dtype. Under
a ``jax.checkpoint`` the kernel's output and entering states carry the name
``ssd_out`` (``models/transformer._REMAT_KEEPS``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.elastic import pallas_interpret, pallas_supported
from mpit_tpu.utils import profiling

_LANE = 128
_NEG_INF = float("-inf")
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


def tiles(chunk: int, heads: int, groups: int, head_dim: int, state: int):
    """Whether the kernels take the shape: a chunk, a state and a group's
    channels (``H / G`` heads of ``P``) that are whole multiples of 128, and
    heads that fill a 128-lane block or whole blocks."""
    return not (chunk % _LANE or state % _LANE
                or (heads // groups * head_dim) % _LANE
                or (_LANE % head_dim and head_dim % _LANE))


def ssd(x, dt, a, b, c, d, *, chunk: int, carry_state: bool = True,
        use_pallas=None):
    """``x``: ``(B, T, H, P)``; ``dt``: ``(B, T, H)`` float32, after its
    softplus; ``a``: ``(H,)`` float32, negative; ``b``, ``c``: ``(B, T, G,
    N)``; ``d``: ``(H,)``. Returns ``y`` ``(B, T, H, P)`` in ``x``'s dtype
    and ``log_decay_min``, a float32 scalar. Any ``T``: the last chunk is
    padded with steps of ``dt = 0``, which neither decay nor feed the state.

    ``use_pallas``: None = the kernels where the backend is a TPU, the
    shape tiles (``tiles``: ``chunk``, ``N`` and ``H / G · P`` whole
    multiples of 128, ``P`` a divisor or a multiple of 128) and the state
    is carried, the ``jax.numpy`` form
    everywhere else; True = the kernels (compiled on TPU, interpreted on
    CPU) or a ``ValueError`` that names what does not tile; False = the
    ``jax.numpy`` form.

    ``carry_state=False`` leaves the states where they are made (every chunk
    starts from 0): a fault, for the control that the comparison deciding a
    cell's ``correct`` has to refuse (``scripts/nemotron_controls.py``)."""
    h, p = x.shape[2:]
    g, n = b.shape[2:]
    fits = tiles(chunk, h, g, p, n)
    if use_pallas is None:
        use_pallas = pallas_supported() and fits and carry_state
    if not use_pallas:
        return _ssd_chunked(x, dt, a, b, c, d, chunk, carry_state)
    if not fits or not carry_state:
        raise ValueError(
            f"ssd: the kernels want chunk={chunk}, state={n} and a group's "
            f"channels {h // g} x {p} in whole multiples of {_LANE}, a head "
            f"that divides {_LANE} or is a multiple of it, and "
            f"carry_state=True (got {carry_state})")
    return _ssd_kernels(x, dt, a, b, c, d, chunk, pallas_interpret())


def _padded(t: int, q: int, *arrays):
    """``arrays`` with ``T`` (axis 1) padded with zeros to whole chunks."""
    pad = -t % q
    if not pad:
        return arrays
    return tuple(jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                 for v in arrays)


def _ssd_chunked(x, dt, a, b, c, d, chunk, carry_state):
    """The chunked form in ``jax.numpy``, differentiated by jax: the
    specification the kernels are tested against."""
    f32, dtype = jnp.float32, x.dtype
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g  # heads a group
    q = chunk
    x, dt, b, c = _padded(t, q, x, dt, b, c)
    nc = x.shape[1] // q
    mm = lambda spec, u, v: jnp.einsum(
        spec, u.astype(dtype), v.astype(dtype), preferred_element_type=f32)

    # (batch, chunk, step, group, head of the group, ...); the decays with
    # the step last, so that a chunk's Q x Q masks have steps on both of
    # their minor dimensions
    xc = x.reshape(bsz, nc, q, g, r, p)
    bc, cc = b.reshape(bsz, nc, q, g, n), c.reshape(bsz, nc, q, g, n)
    dtc = dt.astype(f32).reshape(bsz, nc, q, g, r)
    xdt = (xc.astype(f32) * dtc[..., None]).astype(dtype)
    cum = jnp.cumsum(
        jnp.moveaxis(dtc, 2, -1) * a.astype(f32).reshape(g, r, 1), axis=-1)
    last = cum[..., -1]  # (B, nc, g, r): a chunk's whole log-decay

    # inside a chunk: (L o C B^T)(dt x)
    i, j = jnp.arange(q)[:, None], jnp.arange(q)[None, :]
    seg = cum[..., :, None] - cum[..., None, :]  # (B, nc, g, r, i, j)
    decay = jnp.exp(jnp.where(i >= j, seg, -jnp.inf))
    cb = mm("zcign,zcjgn->zcgij", cc, bc)
    y = mm("zcgrij,zcjgrp->zcigrp", cb[:, :, :, None] * decay, xdt)

    # a chunk's closing state from nothing, then the carry
    to_end = jnp.moveaxis(jnp.exp(last[..., None] - cum), -1, 2)
    closing = mm("zcjgrp,zcjgn->zcgrpn",
                 xdt.astype(f32) * to_end[..., None], bc)

    def step(state, chunk_in):
        made, log_decay = chunk_in
        return jnp.exp(log_decay)[..., None, None] * state + made, state

    if carry_state:
        _, entering = lax.scan(
            step, jnp.zeros((bsz, g, r, p, n), f32),
            (jnp.moveaxis(closing, 1, 0), jnp.moveaxis(last, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)  # (B, nc, g, r, p, n)
        y = y + mm("zcign,zcgrpn->zcigrp", cc, entering) * jnp.moveaxis(
            jnp.exp(cum), -1, 2)[..., None]

    y = y + xc.astype(f32) * d.astype(f32).reshape(g, r)[..., None]
    y = y.reshape(bsz, nc * q, h, p)[:, :t].astype(dtype)
    return y, lax.stop_gradient(jnp.min(last))


# ---- the kernels ---------------------------------------------------------
#
# One grid step is one chunk of one group of one batch row. Its blocks: the
# group's channels ``x`` ``(Q, r P)``, ``B`` and ``C`` ``(Q, N)``, the heads'
# steps ``dt`` and running log-decays ``cum`` as columns ``(Q, r)`` (a step
# a sublane: what scales a row) and ``cum`` as rows ``(r, Q)`` too (a step a
# lane: the ``j`` of ``L_ij``), ``D`` spread over the channels ``(1, r P)``.
# The state of the group's heads is one f32 scratch ``(N, r P)``, transposed
# and the heads side by side, so that the products with it take every head
# at once and none transposes it. What is a head's alone, the ``Q x Q``
# decays and the two products they mask, is a Python loop over the heads of
# one 128-lane block of channels (two heads of 64), each product taken over
# the whole block and the head's lanes selected.


def _dot(u, v, contract):
    return lax.dot_general(u, v, ((contract[:1], contract[1:]), ((), ())),
                           preferred_element_type=jnp.float32)


def _blocks(p):
    """The channels in lane-aligned blocks: ``(width, heads a block)``."""
    width = max(p, _LANE)
    return width, width // p


def _spread(cols, p):
    """``(rows, r)``, a value a head, to ``(rows, r p)``: each over its
    head's ``p`` channels."""
    rows, r = cols.shape
    width, per = _blocks(p)
    lane_head = lax.broadcasted_iota(jnp.int32, (rows, width), 1) // p
    out = []
    for first in range(0, r, per):
        block = jnp.broadcast_to(cols[:, first:first + 1], (rows, width))
        for m in range(1, per):
            block = jnp.where(lane_head == m,
                              cols[:, first + m:first + m + 1], block)
        out.append(block)
    return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]


def _gather(t, p, r):
    """``(rows, r p)`` float32 to ``(rows, r)``: the sum over each head's
    ``p`` channels, on the MXU against a matrix of ones and zeros. The
    float32 addends go in as three bfloat16 parts (8 + 8 + 8 bits of the
    mantissa), so the sums are float32's."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    select = (lax.broadcasted_iota(jnp.int32, (r * p, _LANE), 0) // p
              == lax.broadcasted_iota(jnp.int32, (r * p, _LANE), 1)
              ).astype(bf16)
    total = jnp.zeros((t.shape[0], _LANE), f32)
    for _ in range(3):
        part = t.astype(bf16)
        total = total + _dot(part, select, (1, 0))
        t = t - part.astype(f32)
    return total[:, :r]


def _shared(x_ref, dt_ref, cumc_ref, p):
    """What forward and backward both build from a step's blocks: ``x``
    (f32), ``dt`` over the channels, ``X = x dt`` (operand dtype), and over
    the channels too the chunk's whole log-decay ``(1, r P)`` and the decays
    ``e = exp(cum)``, ``w = exp(cum_Q - cum)``."""
    x = x_ref[0].astype(jnp.float32)
    step = _spread(dt_ref[0, 0, 0], p)
    cumc = cumc_ref[0, 0, 0]
    cum = _spread(cumc, p)
    last = _spread(cumc[-1:, :], p)
    return (x, step, (x * step).astype(x_ref.dtype), last, jnp.exp(cum),
            jnp.exp(last - cum))


def _masks(q, width, p):
    steps = (lax.broadcasted_iota(jnp.int32, (q, q), 0)
             >= lax.broadcasted_iota(jnp.int32, (q, q), 1))
    return steps, lax.broadcasted_iota(jnp.int32, (q, width), 1) // p


def _decay(h, cumc_ref, cumr_ref, steps):
    """``L_ij = exp(cum_i - cum_j)`` for ``j <= i`` and 0 above, masked
    before the exponential: no exponent is ever positive."""
    return jnp.exp(jnp.where(
        steps, cumc_ref[0, 0, 0, :, h:h + 1] - cumr_ref[0, 0, 0, h:h + 1, :],
        _NEG_INF))


def _fwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, d_ref,
                y_ref, entering_ref, state, *, r, p):
    f32, dtype = jnp.float32, x_ref.dtype
    q = x_ref.shape[1]
    width, per = _blocks(p)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    s_in = state[...]
    entering_ref[0, 0, 0] = s_in
    bb, cc = b_ref[0], c_ref[0]
    x, _, xdt, last, e, w = _shared(x_ref, dt_ref, cumc_ref, p)
    y = e * _dot(cc, s_in.astype(dtype), (1, 0)) + x * d_ref[...]
    state[...] = jnp.exp(last) * s_in + _dot(
        bb, (xdt.astype(f32) * w).astype(dtype), (0, 0))

    cb = _dot(cc, bb, (1, 1))  # (Q, Q): C_i . B_j
    steps, lane_head = _masks(q, width, p)
    for block in range(r // per):
        cols = slice(block * width, (block + 1) * width)
        inside = None
        for m in range(per):
            decay = _decay(block * per + m, cumc_ref, cumr_ref, steps)
            mine = _dot((cb * decay).astype(dtype), xdt[:, cols], (1, 0))
            inside = mine if m == 0 else jnp.where(lane_head == m, mine,
                                                   inside)
        y_ref[0, :, cols] = (y[:, cols] + inside).astype(dtype)


def _bwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, d_ref,
                entering_ref, dy_ref, dx_ref, ddt_ref, dcumc_ref, dcumr_ref,
                db_ref, dc_ref, dd_ref, dstate, *, r, p):
    f32, dtype = jnp.float32, x_ref.dtype
    q = x_ref.shape[1]
    width, per = _blocks(p)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bb, cc = b_ref[0], c_ref[0]
    x, step, xdt, last, e, w = _shared(x_ref, dt_ref, cumc_ref, p)
    dy = dy_ref[0]
    dy32, xdt32 = dy.astype(f32), xdt.astype(f32)
    s_in, ds_out = entering_ref[0, 0, 0], dstate[...]
    s_op, ds_op = s_in.astype(dtype), ds_out.astype(dtype)
    dy_e = (e * dy32).astype(dtype)
    whole = jnp.exp(last)
    dstate[...] = whole * ds_out + _dot(cc, dy_e, (0, 0))

    # through the closing state: B dS_out^T, weighted to the chunk's end
    closing = w * _dot(bb, ds_op, (1, 0))
    # d cum where it scales a row: + what C S_in gave y, - what the step
    # gave the closing state; and the chunk's end takes the whole decay's
    to_steps = _gather(
        dy32 * (e * _dot(cc, s_op, (1, 0))) - closing * xdt32, p, r)
    to_end = _gather(
        jnp.sum(closing * xdt32, axis=0, keepdims=True)
        + whole * jnp.sum(ds_out * s_in, axis=0, keepdims=True), p, r)
    is_end = lax.broadcasted_iota(jnp.int32, (q, r), 0) == q - 1
    dcumc = to_steps + jnp.where(is_end, to_end, 0.0)
    dcumr = jnp.zeros((r, q), f32)

    cb = _dot(cc, bb, (1, 1))
    steps, lane_head = _masks(q, width, p)
    head_lane = lax.broadcasted_iota(jnp.int32, (q, r), 1)
    head_row = lax.broadcasted_iota(jnp.int32, (r, q), 0)
    dcb = jnp.zeros((q, q), f32)
    dxdt = []
    for block in range(r // per):
        cols = slice(block * width, (block + 1) * width)
        inside = None
        for m in range(per):
            h = block * per + m
            decay = _decay(h, cumc_ref, cumr_ref, steps)
            theirs = dy[:, cols]
            if per > 1:
                theirs = jnp.where(lane_head == m, theirs,
                                   jnp.zeros_like(theirs))
            dm = _dot(theirs, xdt[:, cols], (1, 1)) * decay
            dcb = dcb + dm
            pull = dm * cb  # dM o M: what the decay L_ij carries
            dcumc = dcumc + jnp.where(
                head_lane == h, jnp.sum(pull, axis=1, keepdims=True), 0.0)
            dcumr = jnp.where(head_row == h,
                              -jnp.sum(pull, axis=0, keepdims=True), dcumr)
            mine = _dot((cb * decay).astype(dtype), dy[:, cols], (0, 0))
            inside = mine if m == 0 else jnp.where(lane_head == m, mine,
                                                   inside)
        dxdt.append(closing[:, cols] + inside)
    dxdt = jnp.concatenate(dxdt, axis=1) if len(dxdt) > 1 else dxdt[0]

    dx_ref[0] = (dxdt * step + dy32 * d_ref[...]).astype(dtype)
    ddt_ref[0, 0, 0] = _gather(dxdt * x, p, r)
    dd_ref[0] += jnp.sum(dy32 * x, axis=0, keepdims=True)
    dcumc_ref[0, 0, 0] = dcumc
    dcumr_ref[0, 0, 0] = dcumr
    dcb = dcb.astype(dtype)
    dc_ref[0] = (_dot(dcb, bb, (1, 0)) + _dot(dy_e, s_op, (1, 1))
                 ).astype(dtype)
    db_ref[0] = (_dot(dcb, cc, (0, 0))
                 + _dot((xdt32 * w).astype(dtype), ds_op, (1, 1))
                 ).astype(dtype)


def _specs(q, r, p, n, chunk_of):
    """Block specs by a grid ``(batch, group, step)``; ``chunk_of(step)`` is
    the chunk a step works on. In order: channels ``(B, T, H P)``, columns
    ``(B, nc, G, Q, r)``, rows ``(B, nc, G, r, Q)``, ``B`` / ``C`` ``(B, T, G
    N)``, ``D`` ``(1, H P)``, states ``(B, nc, G, r P, N)``."""
    return (
        pl.BlockSpec((1, q, r * p), lambda z, g, s: (z, chunk_of(s), g)),
        pl.BlockSpec((1, 1, 1, q, r),
                     lambda z, g, s: (z, chunk_of(s), g, 0, 0)),
        pl.BlockSpec((1, 1, 1, r, q),
                     lambda z, g, s: (z, chunk_of(s), g, 0, 0)),
        pl.BlockSpec((1, q, n), lambda z, g, s: (z, chunk_of(s), g)),
        pl.BlockSpec((1, r * p), lambda z, g, s: (0, g)),
        pl.BlockSpec((1, 1, 1, n, r * p),
                     lambda z, g, s: (z, chunk_of(s), g, 0, 0)),
    )


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def _fwd_call(x, dt, cumc, cumr, b, c, d, r, interpret):
    bsz, nc, g, q, _ = dt.shape
    p, n = x.shape[2] // (g * r), b.shape[2] // g
    chan, col, row, bc, dd, states = _specs(q, r, p, n, lambda s: s)
    with profiling.scope("ssd_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, r=r, p=p),
            grid=(bsz, g, nc),
            in_specs=[chan, col, col, row, bc, bc, dd],
            out_specs=[chan, states],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct((bsz, nc, g, n, r * p), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((n, r * p), jnp.float32)],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(x, dt, cumc, cumr, b, c, d)


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def _bwd_call(x, dt, cumc, cumr, b, c, d, entering, dy, r, interpret):
    bsz, nc, g, q, _ = dt.shape
    p, n = x.shape[2] // (g * r), b.shape[2] // g
    # the chunks in reverse, dS carried in scratch
    chan, col, row, bc, dd, states = _specs(
        q, r, p, n, lambda s: nc - 1 - s)
    f32 = jnp.float32
    with profiling.scope("ssd_bwd"):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, r=r, p=p),
            grid=(bsz, g, nc),
            in_specs=[chan, col, col, row, bc, bc, dd, states, chan],
            out_specs=[
                chan, col, col, row, bc, bc,
                pl.BlockSpec((1, 1, r * p), lambda z, g_, s: (z, 0, g_)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct(dt.shape, f32),
                jax.ShapeDtypeStruct(cumc.shape, f32),
                jax.ShapeDtypeStruct(cumr.shape, f32),
                jax.ShapeDtypeStruct(b.shape, b.dtype),
                jax.ShapeDtypeStruct(c.shape, c.dtype),
                jax.ShapeDtypeStruct((bsz, 1, x.shape[2]), f32),
            ],
            scratch_shapes=[pltpu.VMEM((n, r * p), f32)],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(x, dt, cumc, cumr, b, c, d, entering, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _core(x, dt, cumc, cumr, b, c, d, r, interpret):
    return _fwd_call(x, dt, cumc, cumr, b, c, d, r, interpret)[0]


def _core_fwd(x, dt, cumc, cumr, b, c, d, r, interpret):
    y, entering = _fwd_call(x, dt, cumc, cumr, b, c, d, r, interpret)
    # named where a jax.checkpoint round the caller sees them: a policy
    # that keeps ``ssd_out`` spares the backward a second forward kernel
    y = checkpoint_name(y, "ssd_out")
    entering = checkpoint_name(entering, "ssd_out")
    return y, (x, dt, cumc, cumr, b, c, d, entering)


def _core_bwd(r, interpret, res, dy):
    *grads, dd = _bwd_call(*res, dy, r, interpret)
    return (*grads, dd.sum(0))


_core.defvjp(_core_fwd, _core_bwd)


def _ssd_kernels(x, dt, a, b, c, d, chunk, interpret):
    """The layouts round ``_core`` in ``jax.numpy``, differentiated by jax:
    the padding, ``cum`` (a chunk's running sum of ``dt A``) as columns and
    as rows, ``D`` spread over its head's channels, ``log_decay_min``."""
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r, q = h // g, chunk
    x, dt, b, c = _padded(t, q, x, dt, b, c)
    nc = x.shape[1] // q
    dtc = jnp.moveaxis(dt.astype(f32).reshape(bsz, nc, q, g, r), 2, 3)
    # the running sum as a product with a triangle of ones, in float32
    # (six bfloat16 passes): XLA's reversed cumsum, this one's transpose,
    # took 1.9 ms a layer on the chip with the heads minor, the product
    # takes under 0.1 (PERF.md section 6, PR 33)
    cumc = jnp.einsum(
        "ij,zcgjr->zcgir", jnp.tril(jnp.ones((q, q), f32)),
        dtc * a.astype(f32).reshape(g, 1, r), precision=lax.Precision.HIGHEST)
    y = _core(
        x.reshape(bsz, nc * q, h * p), dtc, cumc, jnp.swapaxes(cumc, 3, 4),
        b.reshape(bsz, nc * q, g * n), c.reshape(bsz, nc * q, g * n),
        jnp.repeat(d.astype(f32), p)[None], r, interpret)
    return (y.reshape(bsz, nc * q, h, p)[:, :t],
            lax.stop_gradient(jnp.min(cumc[:, :, :, -1])))

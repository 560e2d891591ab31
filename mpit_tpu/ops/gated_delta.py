"""The gated delta rule over a sequence, in chunks (the WY / UT transform).

Sources: Yang, Kautz, Hatamizadeh, "Gated Delta Networks: Improving Mamba2
with Delta Rule", arXiv:2412.06464 (the recurrence and its chunked form);
OLMo 2, arXiv:2501.00656 (the block round it, ``models/transformer.py``).

The linear-attention mixer this serves (``Block._linear_attention_mixer``),
input ``u`` ``(B, T, d)``, ``H`` heads, keys of ``d_k``, values of ``d_v``:

1. ``q = SiLU(conv(u W_q))``, ``k = SiLU(conv(u W_k))``, ``v = SiLU(conv(u
   W_v))``: three depthwise causal convolutions, no bias; reshaped to heads.
2. ``q <- q / |q|_2 · d_k^-1/2``, ``k <- k / |k|_2`` a head and step (L2,
   epsilon 1e-6, float32).
3. ``beta_t = sigmoid(u W_b)`` a head, times 2 where negative eigenvalues are
   allowed (``beta`` in (0, 2): ``I - beta k k^T`` has an eigenvalue in (-1,
   1)); ``g_t = -exp(A_log) · softplus(u W_a + dt_bias)`` a head, the log
   decay, never positive; float32.
4. A state ``S`` in ``R^{d_v x d_k}`` a head, ``S_0 = 0``::

       S' = exp(g_t) S_{t-1}
       S_t = S' + beta_t (v_t - S' k_t) k_t^T        o_t = S_t q_t

5. ``y = RMSNorm_head(o) · w_norm · SiLU(u W_gate)``, then ``W_o``.

This module is item 4: :func:`gated_delta` takes ``q``, ``k`` (after item 2),
``v``, ``g`` and ``beta`` and returns ``o``. Unlike Mamba-2's recurrence
(``ops/ssd.py``: a scalar decay and a rank-one ADDITION) the update first
READS the state (``S' k_t``), so a chunk is not masked products alone: it
needs a triangular solve. In chunks of ``C`` steps, with ``gamma_i`` the sum of
``g`` over the chunk's steps up to and including ``i``, rows a step, and the
state held transposed (``S^T``, ``d_k x d_v``):

- ``A = tril_strict(diag(beta) (K K^T ⊙ exp(gamma_i - gamma_j)))``;
- ``T = (I + A)^-1 diag(beta)``; ``W = T (K ⊙ exp(gamma))``, ``U = T V``;
- with the entering state: ``V_new = U - W S^T``; ``O = (Q ⊙ exp(gamma)) S^T +
  tril(Q K^T ⊙ exp(gamma_i - gamma_j)) V_new``;
- ``S^T_out = exp(gamma_C) S^T + (K ⊙ exp(gamma_C - gamma))^T V_new``.

(Unrolled, ``S_t = exp(gamma_t) S + sum_{j<=t} exp(gamma_t - gamma_j) u_j
k_j^T`` with ``u_t = beta_t (v_t - S'_t k_t)``; putting ``S'_t`` in gives ``(I
+ A) V_new = diag(beta) (V - (K ⊙ exp(gamma)) S^T)``, the rows of ``V_new``
being the ``u_t``.) Only ``V_new`` and the state depend on the chunk before,
so the chunks are taken in order, the state carried (in the ``jax.numpy`` form
a ``lax.scan`` that makes two products a step, everything else one batched
product over a segment's chunks; ``SEGMENT``: an outer scan takes the sequence
a segment at a time under a ``jax.checkpoint``, which bounds what the backward
holds).

**The inverse.** ``I + A`` is unit lower triangular. Its inverse is taken by
halves: the inverse of ``[[L11, 0], [L21, L22]]`` is ``[[L11^-1, 0], [-L22^-1
L21 L11^-1, L22^-1]]``, from blocks of 1 up to the chunk, ``log2 C`` levels of
two batched products each (twelve for ``C = 64``), float32 at
``Precision.HIGHEST``. Every intermediate is a block of the true inverse, whose
size the recurrence bounds (each ``I - beta k k^T`` is a contraction), where the
product form ``(I - A)(I + A^2)(I + A^4)...`` passes through powers of ``A``
that grow as ``C(63, n) |A_ij|^n`` before they vanish and a row-by-row
substitution is ``C`` dependent steps a chunk. ``C`` must be a power of two.

**The repo's rule for decays** (``ops/ssd.py``) holds: every exponent is a sum
or a masked difference that is never positive (``gamma_i``, ``gamma_i -
gamma_j`` for ``j <= i``, ``gamma_C - gamma_j``), the mask applied BEFORE the
exponential, nothing is ever divided by a decay, so nothing overflows however
strong a chunk's decay; ``log_decay_min``, the most negative ``gamma_C`` met,
says whether a form factorised as ``exp(gamma_i) · exp(-gamma_j)`` would
(below about -88 in float32). Decays, their sums, the inverse and the carried
state are float32; the other products take their operands in ``v``'s dtype
and accumulate in float32.

**What runs where.** ``gated_delta`` chooses for itself, from what it can
observe, as ``ssd`` and ``flash_attention`` do. Where the backend is a TPU and
the shape tiles (``tiles``: a chunk that is a power of two from 16 to 128,
``d_k`` and ``d_v`` whole multiples of 16) it runs two Pallas kernels under
one ``jax.custom_vjp``, in a trace ``delta_fwd`` and ``delta_bwd``: a grid of
(batch, heads / ``_HEADS``, chunk), the chunks in order (backward in reverse),
the heads' ``d_k x d_v`` states carried from chunk to chunk in a float32 VMEM
scratch. A chunk's decays, ``A``, the inverse, ``W``, ``U`` and ``V_new`` are
built, used and dropped in VMEM. The inverse there: forward substitution
inside the 16 x 16 diagonal blocks (15 dependent steps of one multiply and
one subtraction over all the blocks of two heads' chunks at once, side by
side on the lanes), then by halves above them (two float32 products a
level, 16 -> 32 -> 64, both heads' in one); every intermediate is still a
block of the true inverse. The forward also writes each chunk's
entering state, the backward's residual; the backward carries ``dS``,
rebuilds the chunk (the same ``_chunk``) and returns the cotangents of ``q``,
``k``, ``v``, ``gamma`` and ``beta``; with ``T = (I + A)^-1`` at hand the
solve's backward is products, ``dA = -tril_strict(T^T dT T^T)``. Everywhere
else (the CPU, the rehearsal's chunk of 8) it runs the chunked form above in
``jax.numpy`` with its segment scan, differentiated by jax, which is the
specification the kernels are tested against
(``tests/test_gated_delta_kernel.py``, interpret mode), ``unit_lower_inverse``
with it. Round the kernels, in ``jax.numpy`` and differentiated by jax: the
padding of a short last chunk, the heads before the steps (``(B, H, T, d)``),
``gamma`` as a product with a triangle of ones, ``log_decay_min``. The kernels
keep the rule for decays (backward too) and the precision: decays, their
sums, the inverse and the state float32, the other products' operands ``v``'s
dtype, rounded where the ``jax.numpy`` form rounds them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.elastic import pallas_interpret, pallas_supported
from mpit_tpu.utils import profiling

_HIGHEST = lax.Precision.HIGHEST


def unit_lower_inverse(lower):
    """The inverse of ``I + lower`` for a strictly lower triangular
    ``lower`` ``(..., C, C)``, ``C`` a power of two, by halves (float32)."""
    c = lower.shape[-1]
    lead = lower.shape[:-2]
    # the diagonal blocks' inverses, size s: (..., C / s, s, s)
    inv = jnp.ones((*lead, c, 1, 1), jnp.float32)
    s = 1
    while s < c:
        n = c // (2 * s)
        pairs = jnp.moveaxis(jnp.diagonal(
            lower.reshape(*lead, n, 2 * s, n, 2 * s), axis1=-4, axis2=-2),
            -1, -3)  # the diagonal blocks of size 2 s: (..., n, 2 s, 2 s)
        inv = inv.reshape(*lead, n, 2, s, s)
        upper, under = inv[..., 0, :, :], inv[..., 1, :, :]
        corner = -jnp.matmul(
            jnp.matmul(under, pairs[..., s:, :s], precision=_HIGHEST),
            upper, precision=_HIGHEST)
        inv = jnp.concatenate([
            jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
            jnp.concatenate([corner, under], axis=-1)], axis=-2)
        s *= 2
    return inv.reshape(*lead, c, c)


#: chunks a segment: the chunked form is taken a segment at a time under a
#: ``jax.checkpoint``, so that its backward keeps the state between segments
#: and rebuilds a segment's ``C x C`` matrices, ``W``, ``U`` and chunk states
#: instead of holding all of them (3 GB a layer at 8,192 tokens, 30 heads)
SEGMENT = 16


def _segment(state, q, k, v, g, beta):
    """The chunked form over one segment: ``q``, ``k`` ``(B, n, H, C, d_k)``,
    ``v`` ``(B, n, H, C, d_v)``, ``g``, ``beta`` ``(B, n, H, C)`` float32,
    ``state`` ``(B, H, d_k, d_v)`` float32 entering. Returns the state
    leaving and ``o`` ``(B, n, H, C, d_v)`` in ``v``'s dtype."""
    f32, dtype = jnp.float32, v.dtype
    c = q.shape[-2]
    mm = lambda spec, a, b: jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype), preferred_element_type=f32)
    gamma = jnp.cumsum(g, axis=-1)
    last = gamma[..., -1]  # a chunk's whole log decay
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    seg = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.exp(jnp.where(i >= j, seg, -jnp.inf))  # j <= i, else 0
    from_start = jnp.exp(gamma)[..., None]
    to_end = jnp.exp(last[..., None] - gamma)[..., None]

    kk = mm("zchid,zchjd->zchij", k, k)
    a = jnp.where(i > j, beta[..., None] * kk * decay, 0.0)
    solve = unit_lower_inverse(a) * beta[..., None, :]  # (I + A)^-1 diag(beta)
    w = mm("zchij,zchjd->zchid", solve, k.astype(f32) * from_start)
    u = mm("zchij,zchjd->zchid", solve, v)
    within = mm("zchid,zchjd->zchij", q, k) * decay  # tril(Q K^T ⊙ decay)
    k_end = (k.astype(f32) * to_end).astype(dtype)

    def step(state, at):
        w_c, u_c, k_c, log_decay = at
        v_new = u_c - mm("zhid,zhde->zhie", w_c, state)
        made = mm("zhid,zhie->zhde", k_c, v_new)
        return jnp.exp(log_decay)[..., None, None] * state + made, (
            state, v_new.astype(dtype))

    chunks = lambda arr: jnp.moveaxis(arr, 1, 0)
    state, (entering, v_new) = lax.scan(
        step, state,
        (chunks(w.astype(dtype)), chunks(u), chunks(k_end), chunks(last)))
    entering, v_new = jnp.moveaxis(entering, 0, 1), jnp.moveaxis(v_new, 0, 1)
    o = (mm("zchid,zchde->zchie", q.astype(f32) * from_start, entering)
         + mm("zchij,zchjd->zchid", within, v_new))
    return state, o.astype(dtype)


def tiles(chunk: int, key_dim: int, value_dim: int) -> bool:
    """Whether the kernels take the shape: a chunk that is a power of two
    from ``_BLOCK`` steps (the solve's diagonal blocks, a bfloat16 tile's
    sublanes) to 128 (a chunk's steps lie on one register's lanes), keys and
    values in whole tiles of 16 sublanes (the state is ``d_k x d_v``, its
    operand form bfloat16)."""
    return not (chunk < _BLOCK or chunk > _LANE or chunk & (chunk - 1)
                or key_dim % 16 or value_dim % 16)


def gated_delta(q, k, v, g, beta, *, chunk: int, use_pallas=None):
    """``q``, ``k``: ``(B, T, H, d_k)`` (normalised by the caller); ``v``:
    ``(B, T, H, d_v)``; ``g``: ``(B, T, H)`` float32 log decays, never
    positive; ``beta``: ``(B, T, H)`` float32. Returns ``o`` ``(B, T, H,
    d_v)`` in ``v``'s dtype and ``log_decay_min``, a float32 scalar. Any
    ``T``: it is padded with steps of ``g = 0`` and ``beta = 0``, which
    neither decay nor write the state (to whole chunks for the kernels, to
    whole segments of ``SEGMENT`` chunks for the ``jax.numpy`` form).

    ``use_pallas``: None = the kernels where the backend is a TPU and the
    shape tiles (``tiles``: ``chunk`` a power of two from 16 to 128, ``d_k``
    and ``d_v`` whole multiples of 16), the ``jax.numpy`` form everywhere
    else; True = the kernels (compiled on TPU, interpreted on CPU) or a
    ``ValueError`` that names what does not tile; False = the ``jax.numpy``
    form."""
    c = chunk
    if c < 1 or c & (c - 1):
        raise ValueError(f"gated_delta: chunk {c} is not a power of two")
    dk, dv = q.shape[-1], v.shape[-1]
    fits = tiles(c, dk, dv)
    if use_pallas is None:
        use_pallas = pallas_supported() and fits
    if not use_pallas:
        return _gated_delta_chunked(q, k, v, g, beta, c)
    if not fits:
        raise ValueError(
            f"gated_delta: the kernels want a chunk of {_BLOCK} to {_LANE} "
            f"steps (got {c}) and keys and values in whole multiples of 16 "
            f"(got d_k={dk}, d_v={dv})")
    return _gated_delta_kernels(q, k, v, g, beta, c, pallas_interpret())


def _padded(t: int, whole: int, *arrays):
    """``arrays`` with ``T`` (axis 1) padded with zeros to a multiple of
    ``whole``."""
    pad = -t % whole
    if not pad:
        return arrays
    return tuple(jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                 for a in arrays)


def _gated_delta_chunked(q, k, v, g, beta, c):
    """The chunked form in ``jax.numpy``, differentiated by jax: the
    specification the kernels are tested against."""
    f32 = jnp.float32
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    n = min(SEGMENT, -(-t // c))  # chunks a segment
    q, k, v, g, beta = _padded(
        t, n * c, q, k, v, g.astype(f32), beta.astype(f32))
    segments = q.shape[1] // (n * c)
    # (segment, batch, chunk, head, step, ...): a chunk's C x C masks have
    # steps on both of their minor dimensions
    cut = lambda a: jnp.moveaxis(
        a.reshape(bsz, segments, n, c, h, *a.shape[3:]), (1, 3), (0, 4))
    body = jax.checkpoint(lambda state, at: _segment(state, *at))
    _, o = lax.scan(body, jnp.zeros((bsz, h, dk, dv), f32),
                    tuple(cut(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, (0, 4), (1, 3)).reshape(bsz, -1, h, dv)[:, :t]
    low = jnp.min(jnp.sum(g.reshape(bsz, -1, c, h), axis=2))
    return o, lax.stop_gradient(low)


# ---- the kernels ---------------------------------------------------------
#
# One grid step is one chunk of ``G`` heads of one batch row (``G``:
# ``_heads_a_step``); the chunks of a row of the grid come in order (backward
# in reverse). Its blocks: ``q``, ``k`` ``(G, C, d_k)`` and ``v``, ``o`` ``(G,
# C, d_v)`` from arrays laid out ``(B, H, T, d)``; a chunk's running log
# decays ``gamma`` and ``beta`` as rows ``(G, 1, C)`` (a step a lane: the
# ``j`` of ``decay_ij``), from which the kernel makes the columns (a step a
# sublane: what scales a row) by a masked sum; the heads' states ``S^T``
# ``(G, d_k, d_v)`` float32 in a VMEM scratch. What a head's chunk needs is
# two-dimensional and the heads of a step are a Python loop, but for the
# two places where a ``C x C`` matrix meets float32 products, the inverse
# and its backward: there ``128 / C`` heads lie side by side on the lanes
# (``_side_by_side``), so that a register and a pass through the MXU carry
# two heads of 64 steps and not one.

#: the solve's diagonal blocks, forward substitution inside (the sweep's
#: ``--blocks``: 16 read under 32 and 64)
_BLOCK = 16
_LANE = 128
_NEG_INF = float("-inf")
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2 ** 20,
)


#: heads a grid step, at most (``scripts/gated_delta_sweep.py --heads``: at
#: the cell's shape 10 read 6% under 6 and 15 over both, PERF.md section 6)
_HEADS = 10


def _heads_a_step(heads: int) -> int:
    """The most heads, up to ``_HEADS``, that divide ``heads``."""
    return max(g for g in range(1, _HEADS + 1) if heads % g == 0)


def _dot(a, b, contract, precision=None):
    return lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _side_by_side(mats):
    """Groups of ``C x C`` matrices side by side on the lanes, ``(C, 128)``
    a group (zeros where the heads run out)."""
    c = mats[0].shape[0]
    per = _LANE // c
    for first in range(0, len(mats), per):
        group = list(mats[first:first + per])
        spare = _LANE - len(group) * c
        if spare:
            group.append(jnp.zeros((c, spare), jnp.float32))
        yield group[0] if len(group) == 1 else jnp.concatenate(group, axis=1)


def _heads_diagonal(wide, c):
    """``(C, 128)``, heads side by side, to ``(128, 128)``: head ``i``'s
    matrix at rows and lanes ``i C`` on, zeros elsewhere, so that one product
    with it is every head's own."""
    lane_head = lax.broadcasted_iota(jnp.int32, wide.shape, 1) // c
    return jnp.concatenate([jnp.where(lane_head == i, wide, 0.0)
                            for i in range(_LANE // c)], axis=0)


def _inverses_in_vmem(lowers):
    """``(I + a)^-1`` for each strictly lower triangular ``a`` ``(C, C)``
    float32 of a list, ``128 / C`` of them side by side on the lanes. Inside
    the ``_BLOCK x _BLOCK`` diagonal blocks by forward substitution: the
    blocks of all those heads lie side by side, ``X`` starts as the identity
    in each, and step ``j`` takes ``a``'s column ``j`` of every block (one
    gather along the lanes) times ``X``'s row ``j`` from the rows under it,
    ``_BLOCK - 1`` dependent steps of one multiply and one subtraction. Above
    the blocks by halves (``unit_lower_inverse``'s rule, two products a
    level at float32, every head's own through ``_heads_diagonal``). Every
    intermediate is a block of the true inverse."""
    c = lowers[0].shape[0]
    blk = min(_BLOCK, c)
    iota = lambda rows, axis: lax.broadcasted_iota(
        jnp.int32, (rows, _LANE), axis)
    row, col = iota(c, 0), iota(c, 1) % c
    # in the blocks' rows: which lanes are block b's; each lane's block's
    # first lane; the identity in every block
    in_block = [iota(blk, 1) % c // blk == b for b in range(c // blk)]
    first_lane = iota(blk, 1) // blk * blk
    eye = (iota(blk, 0) == iota(blk, 1) % blk).astype(jnp.float32)
    out = []
    for a in _side_by_side(lowers):
        # block b's rows hold it at its own lanes: all of them in blk rows
        packed = sum(jnp.where(mine, a[b * blk:(b + 1) * blk], 0.0)
                     for b, mine in enumerate(in_block))
        x = eye
        for j in range(blk - 1):
            column = jnp.take_along_axis(packed, first_lane + j, axis=1)
            x = x - column * jnp.broadcast_to(x[j:j + 1], x.shape)
        x = jnp.concatenate([jnp.where(mine, x, 0.0) for mine in in_block],
                            axis=0)
        s = blk
        while s < c:
            # a pair's block under its diagonal: [[X1, 0], [-X2 L21 X1, X2]]
            under = ((row // s) % 2 == 1) & (col // s == row // s - 1)
            x = x - _dot(
                _dot(x, _heads_diagonal(jnp.where(under, a, 0.0), c), (1, 0),
                     _HIGHEST), _heads_diagonal(x, c), (1, 0), _HIGHEST)
            s *= 2
        out += [x[:, i * c:(i + 1) * c] for i in range(_LANE // c)]
    return out[:len(lowers)]


def _inverses_pulled_back(inverses, cotangents):
    """``-T^T dT T^T`` a head, the solve's backward, the heads side by side
    as in ``_inverses_in_vmem``: two float32 products a group."""
    c = inverses[0].shape[0]
    head = lambda axis: lax.broadcasted_iota(
        jnp.int32, (_LANE, _LANE), axis) // c
    out = []
    for t, dt in zip(_side_by_side(inverses), _side_by_side(cotangents)):
        # (128, 128): t_i^T dT_j at rows i C on and lanes j C on
        left = jnp.where(head(0) == head(1),
                         _dot(t, dt, (0, 0), _HIGHEST), 0.0)
        # (128, C): head i's rows hold (t_i^T dT_i) t_i^T
        both = _dot(left, t, (1, 1), _HIGHEST)
        out += [-both[i * c:(i + 1) * c] for i in range(_LANE // c)]
    return out[:len(inverses)]


def _to_column(values, eye):
    """``(1, C)`` to ``(C, 1)``."""
    return jnp.sum(jnp.where(eye, values, 0.0), axis=1, keepdims=True)


def _to_row(values, eye):
    """``(C, 1)`` to ``(1, C)``."""
    return jnp.sum(jnp.where(eye, values, 0.0), axis=0, keepdims=True)


def _lower(k, gamma, beta, row, col):
    """One head's chunk up to the matrix to invert: a dict by the
    docstring's names, the decays and ``A`` among it, float32. ``row``,
    ``col``: the ``(C, C)`` index masks are built from."""
    c = k.shape[0]
    eye, under = row == col, row > col
    gamma_col, beta_col = _to_column(gamma, eye), _to_column(beta, eye)
    # masked before the exponential: no exponent is ever positive
    decay = jnp.exp(jnp.where(row >= col, gamma_col - gamma, _NEG_INF))
    last = gamma[:, c - 1:]  # (1, 1): the chunk's whole log decay
    kk = _dot(k, k, (1, 1))
    return dict(
        under=under, eye=eye, beta_col=beta_col, decay=decay,
        whole=jnp.exp(last), from_start=jnp.exp(gamma_col),
        to_end=jnp.exp(last - gamma_col), k32=k.astype(jnp.float32), kk=kk,
        a=jnp.where(under, beta_col * kk * decay, 0.0))


def _chunk(m, t, q, k, v, beta, state):
    """The rest of what forward and backward both build of one head's
    chunk, from ``_lower``'s dict ``m`` and the inverse ``t``, added to the
    dict. Float32 what scales by a decay; a product's operands in ``v``'s
    dtype (``*_op``)."""
    f32, dtype = jnp.float32, v.dtype
    tb_op = (t * beta).astype(dtype)  # (I + A)^-1 diag(beta)
    ke_op = (m["k32"] * m["from_start"]).astype(dtype)
    w_op = _dot(tb_op, ke_op, (1, 0)).astype(dtype)
    state_op = state.astype(dtype)
    v_new = _dot(tb_op, v, (1, 0)) - _dot(w_op, state_op, (1, 0))
    qk = _dot(q, k, (1, 1))
    return dict(
        m, t=t, tb_op=tb_op, ke_op=ke_op, w_op=w_op, state_op=state_op,
        v_new_op=v_new.astype(dtype), qk=qk,
        within_op=(qk * m["decay"]).astype(dtype),
        qe_op=(q.astype(f32) * m["from_start"]).astype(dtype),
        kf_op=(m["k32"] * m["to_end"]).astype(dtype))


def _chunks(q_ref, k_ref, v_ref, gamma_ref, beta_ref, states):
    """``_chunk``'s dict for each head of the step, the inverses taken
    side by side."""
    heads, c = range(q_ref.shape[1]), q_ref.shape[2]
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lowers = [_lower(k_ref[0, h], gamma_ref[0, 0, h], beta_ref[0, 0, h],
                     row, col) for h in heads]
    inverses = _inverses_in_vmem([m["a"] for m in lowers])
    return [_chunk(lowers[h], inverses[h], q_ref[0, h], k_ref[0, h],
                   v_ref[0, h], beta_ref[0, 0, h], states[h]) for h in heads]


def _fwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, o_ref,
                entering_ref, state):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    entering = [state[h] for h in range(q_ref.shape[1])]
    for h, m in enumerate(_chunks(q_ref, k_ref, v_ref, gamma_ref, beta_ref,
                                  entering)):
        entering_ref[0, 0, h] = entering[h]
        o = (_dot(m["qe_op"], m["state_op"], (1, 0))
             + _dot(m["within_op"], m["v_new_op"], (1, 0)))
        o_ref[0, h] = o.astype(o_ref.dtype)
        state[h] = m["whole"] * entering[h] + _dot(
            m["kf_op"], m["v_new_op"], (0, 0))


def _bwd_kernel(q_ref, k_ref, v_ref, gamma_ref, beta_ref, entering_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dgamma_ref, dbeta_ref,
                dstate):
    f32, dtype = jnp.float32, v_ref.dtype
    c = q_ref.shape[2]
    is_end = lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    entering = [entering_ref[0, 0, h] for h in range(q_ref.shape[1])]
    chunks = _chunks(q_ref, k_ref, v_ref, gamma_ref, beta_ref, entering)
    for h, m in enumerate(chunks):
        do, ds = do_ref[0, h], dstate[h]
        ds_op = ds.astype(dtype)
        # through o and the state leaving, to V_new and the state entering
        dv_new_op = (_dot(m["within_op"], do, (0, 0))
                     + _dot(m["kf_op"], ds_op, (1, 0))).astype(dtype)
        dstate[h] = (m["whole"] * ds + _dot(m["qe_op"], do, (0, 0))
                     - _dot(m["w_op"], dv_new_op, (0, 0)))
        # through W = T (K . exp(gamma)) and U = T V, T = (I + A)^-1 beta
        dw_op = (-_dot(dv_new_op, m["state_op"], (1, 1))).astype(dtype)
        dtb = (_dot(dw_op, m["ke_op"], (1, 1))
               + _dot(dv_new_op, v_ref[0, h], (1, 1)))
        dv_ref[0, h] = _dot(m["tb_op"], dv_new_op, (0, 0)).astype(dtype)
        m.update(
            ds=ds, ds_op=ds_op, dw_op=dw_op, dtb=dtb,
            dp=_dot(do, m["v_new_op"], (1, 1)),  # d tril(Q K^T . decay)
            dqe=_dot(do, m["state_op"], (1, 1)))  # d (Q . exp(gamma))
    # the solve's backward is products: dA = -T^T dT T^T under the diagonal
    pulled = _inverses_pulled_back(
        [m["t"] for m in chunks],
        [m["dtb"] * beta_ref[0, 0, h] for h, m in enumerate(chunks)])
    for h, m in enumerate(chunks):
        q, k = q_ref[0, h], k_ref[0, h]
        eye, decay, k32 = m["eye"], m["decay"], m["k32"]
        from_start, to_end = m["from_start"], m["to_end"]
        dp, dqe, ds = m["dp"], m["dqe"], m["ds"]
        da = jnp.where(m["under"], pulled[h], 0.0)
        dkk_op = (da * m["beta_col"] * decay).astype(dtype)
        dpd_op = (dp * decay).astype(dtype)
        dke = _dot(m["tb_op"], m["dw_op"], (0, 0))
        # d (K . exp(gamma_C - gamma))
        dkf = _dot(m["v_new_op"], m["ds_op"], (1, 1))
        dq_ref[0, h] = (dqe * from_start + _dot(dpd_op, k, (1, 0))
                        ).astype(dtype)
        dk_ref[0, h] = (
            dke * from_start + dkf * to_end + _dot(dkk_op, k, (1, 0))
            + _dot(dkk_op, k, (0, 0)) + _dot(dpd_op, q, (0, 0))
        ).astype(dtype)

        # beta: where it scales T's columns (a row) and A's rows (a column)
        dbeta_ref[0, 0, h] = (
            jnp.sum(m["dtb"] * m["t"], axis=0, keepdims=True) + _to_row(
                jnp.sum(da * m["kk"] * decay, axis=1, keepdims=True), eye))
        # gamma: what each decay carries, d decay_ij decay_ij, to its row
        # and from its column; the three scalings; the chunk's whole decay
        pull = dp * m["qk"] * decay + da * m["a"]
        to_end_pull = jnp.sum(dkf * k32 * to_end, axis=1, keepdims=True)
        columns = (jnp.sum(pull, axis=1, keepdims=True)
                   + jnp.sum((dqe * q.astype(f32) + dke * k32) * from_start,
                             axis=1, keepdims=True) - to_end_pull)
        at_end = (jnp.sum(to_end_pull, axis=0, keepdims=True)
                  + m["whole"] * jnp.sum(
                      jnp.sum(ds * entering[h], axis=1, keepdims=True),
                      axis=0, keepdims=True))
        dgamma_ref[0, 0, h] = (
            _to_row(columns, eye) - jnp.sum(pull, axis=0, keepdims=True)
            + jnp.where(is_end, at_end, 0.0))


def _specs(g, c, dk, dv, chunk_of):
    """Block specs by a grid ``(batch, heads / g, step)``; ``chunk_of(step)``
    is the chunk a step works on. In order: keys ``(B, H, T, d_k)``, values
    ``(B, H, T, d_v)``, rows ``(B, nc, H, 1, C)``, states ``(B, nc, H, d_k,
    d_v)``."""
    steps = lambda d: pl.BlockSpec(
        (1, g, c, d), lambda z, hg, s: (z, hg, chunk_of(s), 0))
    chunks = lambda *minor: pl.BlockSpec(
        (1, 1, g, *minor), lambda z, hg, s: (z, chunk_of(s), hg, 0, 0))
    return steps(dk), steps(dv), chunks(1, c), chunks(dk, dv)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_call(q, k, v, gamma, beta, interpret):
    bsz, h, _, dk = q.shape
    dv, (nc, c) = v.shape[-1], (gamma.shape[1], gamma.shape[-1])
    g = _heads_a_step(h)
    keys, values, rows, states = _specs(g, c, dk, dv, lambda s: s)
    with profiling.scope("delta_fwd"):
        return pl.pallas_call(
            _fwd_kernel,
            grid=(bsz, h // g, nc),
            in_specs=[keys, keys, values, rows, rows],
            out_specs=[values, states],
            out_shape=[
                jax.ShapeDtypeStruct(v.shape, v.dtype),
                jax.ShapeDtypeStruct((bsz, nc, h, dk, dv), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((g, dk, dv), jnp.float32)],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(q, k, v, gamma, beta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_call(q, k, v, gamma, beta, entering, do, interpret):
    bsz, h, _, dk = q.shape
    dv, (nc, c) = v.shape[-1], (gamma.shape[1], gamma.shape[-1])
    g = _heads_a_step(h)
    # the chunks in reverse, dS carried in scratch
    keys, values, rows, states = _specs(g, c, dk, dv, lambda s: nc - 1 - s)
    with profiling.scope("delta_bwd"):
        return pl.pallas_call(
            _bwd_kernel,
            grid=(bsz, h // g, nc),
            in_specs=[keys, keys, values, rows, rows, states, values],
            out_specs=[keys, keys, values, rows, rows],
            out_shape=[
                jax.ShapeDtypeStruct(q.shape, q.dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
                jax.ShapeDtypeStruct(gamma.shape, jnp.float32),
                jax.ShapeDtypeStruct(beta.shape, jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((g, dk, dv), jnp.float32)],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(q, k, v, gamma, beta, entering, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _core(q, k, v, gamma, beta, interpret):
    return _fwd_call(q, k, v, gamma, beta, interpret)[0]


def _core_fwd(q, k, v, gamma, beta, interpret):
    o, entering = _fwd_call(q, k, v, gamma, beta, interpret)
    return o, (q, k, v, gamma, beta, entering)


def _core_bwd(interpret, res, do):
    return tuple(_bwd_call(*res, do, interpret))


_core.defvjp(_core_fwd, _core_bwd)


def _gated_delta_kernels(q, k, v, g, beta, c, interpret):
    """The layouts round ``_core`` in ``jax.numpy``, differentiated by jax:
    the padding to whole chunks, the heads before the steps, ``gamma`` (a
    chunk's running sum of ``g``) and ``beta`` as a row a chunk and head,
    ``log_decay_min``."""
    f32 = jnp.float32
    bsz, t, h, _ = q.shape
    q, k, v, g, beta = _padded(t, c, q, k, v, g.astype(f32), beta.astype(f32))
    heads_first = lambda a: jnp.swapaxes(a, 1, 2)
    # a chunk's steps last: (B, nc, H, C)
    rows = lambda a: jnp.moveaxis(a.reshape(bsz, -1, c, h), 2, 3)
    # the running sum as a product with a triangle of ones, in float32
    # (ops/ssd.py: XLA's cumsum over a short axis cost more than a scan)
    gamma = jnp.einsum("zchj,ij->zchi", rows(g),
                       jnp.tril(jnp.ones((c, c), f32)), precision=_HIGHEST)
    o = _core(heads_first(q.astype(v.dtype)), heads_first(k.astype(v.dtype)),
              heads_first(v), gamma[:, :, :, None], rows(beta)[:, :, :, None],
              interpret)
    return (heads_first(o)[:, :t],
            lax.stop_gradient(jnp.min(gamma[..., -1])))
